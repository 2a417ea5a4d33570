"""§Perf hillclimb cell 3: the compiled Free Join engine itself (the
paper-representative pair). Wall-clock on CPU (the join engine is the one
component that genuinely runs here), jit-compiled, excluding compile.

Part 1 — hillclimb iterations on the triangle count over zipf-skewed edges
(hypothesis -> change -> measure, EXPERIMENTS.md §Perf):
  J0 baseline            capacities 4M, probe budget 32
  J1 probe budget 8      probe loop is 32 unrolled gather+compare rounds;
                         load factor <= 0.5 => clusters are short; 8 rounds
                         should cut probe work ~4x if probes dominate
  J2 tight capacities    right-size frontier buffers from cardinality
                         estimates (expansion + mask work scales with
                         capacity, not with live rows)
  J3 J1+J2 combined

Part 2 — the planned path vs the eager engine on a low-selectivity star
query (a selective probe kills most frontier lanes early):
  eager                  api.free_join (numpy COLT engine)
  compiled_nocompact     AdaptiveExecutor, planner capacities, no compaction
  compiled_compact       same + frontier compaction at the planner-chosen
                         point (mid-node, right after the selective probe).
                         This is the COLD per-call cost: tries rebuilt
                         in-graph on every call.
  compiled_warm          the same executor fed prebuilt tries from the
                         cross-call TRIE_CACHE (run_relations): the
                         steady-state serving cost, probe work only. The
                         build/probe split is also timed separately (the
                         jit'd build program alone vs the warm probe call)
                         and recorded in BENCH_join_perf.json.

Part 3 — the compiled-distributed path on the same star query: SpmdCounter
(hypercube partition + shard_map + psum, planner capacities per shard) on a
2- and 4-shard mesh of fake CPU devices. Runs in a subprocess so the forced
device count never leaks into this process's jax backend.

Part 4 — bushy plans (PR 4): a three-stage bushy tree over a six-relation
path query, eager vs the PR 3 hybrid (non-root stages on the eager host
engine per call, root compiled) vs the fully-compiled chain (every stage
on device inside one AdaptiveExecutor call).

Part 5 — plan choice (PR 7): greedy left-deep (optimize_level=0) vs the
cost-based bushy enumeration (optimize_level=2) on a four-relation chain
with selective end joins and a dense middle join. The greedy search can
only extend left-deep, so it drags the dense A⋈B⋈C intermediate through
the rest of the plan; the DP brackets it as (A⋈B)⋈(C⋈D) and the device
cost model picks that. Warm steady state (runners built once, tries
cached), interleaved timing.

The rows also land in BENCH_join_perf.json (repo root) so the perf
trajectory of the compiled path is tracked PR-over-PR.
"""
from __future__ import annotations

import json
import time

import numpy as np

import jax

from benchmarks.common import timeit
from repro.core import ExecOptions, binary2fj, factor, free_join
from repro.core.capacity import plan_capacities
from repro.core.compiled import (
    AdaptiveExecutor,
    count_value,
    make_count_fn,
    relations_to_cols,
)
from repro.core.plan import BinaryPlan
from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query, triangle_query


def _data(n=200_000, dom=30_000, seed=0):
    rng = np.random.default_rng(seed)
    q = triangle_query()
    rels = {}
    for a in q.atoms:
        z = ((rng.zipf(1.5, n) - 1) % dom)
        perm = rng.permutation(dom)
        rels[a.alias] = Relation(
            a.alias, {a.vars[0]: perm[z], a.vars[1]: rng.integers(0, dom, n)}
        )
    return q, rels


def _lowsel_data(n=600_000, dom=30_000, sel=0.02, seed=0):
    """Star Q(x,y,a,b) :- R(x,y), S(y,a), T(y,b) where S covers only a
    `sel` fraction of the y domain. The factored plan probes S then T in
    one node; the S probe kills ~98% of the frontier, so without compaction
    the T probe (budget x gather rounds per lane) and both later factorized
    folds drag every dead lane along — the compaction sweet spot."""
    rng = np.random.default_rng(seed)
    q = Query([Atom("R", ("x", "y")), Atom("S", ("y", "a")), Atom("T", ("y", "b"))])
    ny = max(1, int(dom * sel))
    y_live = rng.choice(dom, ny, replace=False)
    rels = {
        "R": Relation("R", {"x": rng.integers(0, dom, n), "y": rng.integers(0, dom, n)}),
        "S": Relation("S", {"y": y_live[rng.integers(0, ny, ny)],
                            "a": rng.integers(0, dom, ny)}),
        "T": Relation("T", {"y": rng.integers(0, dom, n // 10),
                            "b": rng.integers(0, dom, n // 10)}),
    }
    return q, rels


def _run(q, rels, caps, budget, repeats=3):
    import jax.numpy as jnp

    fj = factor(binary2fj(q.atoms, q))
    fn = jax.jit(make_count_fn(fj, caps, impl="jnp", budget=budget))
    cols = {
        a.alias: {v: jnp.asarray(rels[a.alias].columns[v], jnp.int32) for v in a.vars}
        for a in q.atoms
    }
    count, ovf = fn(cols)  # compile + 1st run
    assert not bool(ovf), "capacity overflow"
    t, _ = timeit(lambda: jax.block_until_ready(fn(cols)), repeats=repeats, warmup=1)
    return t, count_value(count)


def _run_adaptive(q, rels, repeats, compact_threshold):
    fj = factor(binary2fj(q.atoms, q))
    planned = plan_capacities(fj, rels, compact_threshold=compact_threshold)
    ex = AdaptiveExecutor(fj, planned, agg="count")
    cols = relations_to_cols(fj, rels)
    count = count_value(ex(cols))  # compile (+ any overflow growth) + 1st run
    t, _ = timeit(lambda: jax.block_until_ready(ex(cols)), repeats=repeats, warmup=1)
    return t, count, ex, planned


def _time_build_program(ex, rels, repeats):
    """Wall time of the jit'd trie build program alone: every base
    relation's trie rebuilt from its (cached) device columns, bypassing the
    trie cache — the per-call cost the warm path amortizes away."""
    from repro.core import compiled as C

    plans = []
    for a, lo in sorted(ex._alias_lops.items()):
        if lo is None:
            continue
        rel = rels[a]
        dev = C.device_columns(rel)
        flat = tuple(v for lv in lo.levels for v in lv)
        used = {v: dev[v] for v in flat}
        plans.append((used, lo))

    def build_all():
        return [C._build_trie_jit(used, lo, ex.impl, ex.budget) for used, lo in plans]

    t, _ = timeit(lambda: jax.block_until_ready(build_all()), repeats=repeats, warmup=1)
    return t


def run(repeats: int = 3, smoke: bool = False):
    q, rels = _data(n=10_000, dom=3_000) if smoke else _data()
    cap = 1 << 17 if smoke else 1 << 22
    tight = [1 << 14, 1 << 16, 1 << 16, 1 << 16] if smoke else [1 << 19, 1 << 21, 1 << 21, 1 << 21]
    rows = []
    # J0
    t0, c0 = _run(q, rels, [cap] * 4, 32, repeats)
    rows.append({"name": "joinperf.J0_baseline", "us": t0 * 1e6, "derived": f"count={c0}"})
    # J1: probe budget 8
    t1, c1 = _run(q, rels, [cap] * 4, 8, repeats)
    assert c1 == c0
    rows.append({"name": "joinperf.J1_budget8", "us": t1 * 1e6,
                 "derived": f"speedup_vs_J0={t0 / t1:.2f}x"})
    # J2: tight capacities (estimate-sized, x2 safety)
    t2, c2 = _run(q, rels, tight, 32, repeats)
    assert c2 == c0
    rows.append({"name": "joinperf.J2_tight_caps", "us": t2 * 1e6,
                 "derived": f"speedup_vs_J0={t0 / t2:.2f}x"})
    # J3: both
    t3, c3 = _run(q, rels, tight, 8, repeats)
    assert c3 == c0
    rows.append({"name": "joinperf.J3_combined", "us": t3 * 1e6,
                 "derived": f"speedup_vs_J0={t0 / t3:.2f}x"})
    rows.extend(run_compiled_vs_eager(repeats=repeats, smoke=smoke))
    rows.extend(run_distributed(repeats=repeats, smoke=smoke))
    rows.extend(run_bushy(repeats=repeats, smoke=smoke))
    rows.extend(run_planner(repeats=repeats, smoke=smoke))
    return rows


def run_compiled_vs_eager(
    repeats: int = 3, smoke: bool = False, path: str = "BENCH_join_perf.json"
):
    """Eager vs planned-compiled (with/without compaction) on the
    low-selectivity star query; writes the BENCH_join_perf.json perf record
    (full runs only — smoke numbers don't overwrite the trajectory)."""
    q, rels = _lowsel_data(n=30_000, dom=3_000) if smoke else _lowsel_data()
    te, ce = timeit(lambda: free_join(q, rels, agg="count"), repeats=repeats, warmup=1)
    tn, cn, _, _ = _run_adaptive(q, rels, repeats, compact_threshold=0.0)  # never compact
    tc, cc, ex, planned = _run_adaptive(q, rels, repeats, compact_threshold=0.25)
    # warm (cached-trie) steady state: run_relations serves prebuilt tries
    # from the cross-call cache — pure probe cost per call
    cw = ex.run_relations(rels)  # cold build into the cache + compile
    tw, _ = timeit(lambda: ex.run_relations(rels), repeats=repeats, warmup=1)
    tb = _time_build_program(ex, rels, repeats)
    assert ce == cn == cc == cw, (ce, cn, cc, cw)
    # check the planner's output: adaptive growth may legitimately disable
    # an under-targeted compaction at run time
    assert any(t is not None for t in planned.compact_to), "expected a compaction node"
    rows = [
        {"name": "joinperf.eager_lowsel", "us": te * 1e6, "derived": f"count={ce}"},
        {"name": "joinperf.compiled_nocompact_lowsel", "us": tn * 1e6,
         "derived": f"speedup_vs_eager={te / tn:.2f}x"},
        {"name": "joinperf.compiled_compact_lowsel", "us": tc * 1e6,
         "derived": f"speedup_vs_nocompact={tn / tc:.2f}x;plan={ex.cap_plan}"},
        {"name": "joinperf.compiled_warm_lowsel", "us": tw * 1e6,
         "derived": f"speedup_vs_cold={tc / tw:.2f}x;build_us={tb * 1e6:.0f}"},
    ]
    if smoke:
        return rows
    record = {
        "bench": "join_perf.compiled_vs_eager",
        "query": "star R(x,y),S(y,a),T(y,b), 2% probe selectivity",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "count": ce,
        "eager_us": te * 1e6,
        "compiled_nocompact_us": tn * 1e6,
        "compiled_compact_us": tc * 1e6,
        "compact_speedup_vs_nocompact": tn / tc,
        "compiled_warm_us": tw * 1e6,
        "warm_speedup_vs_cold": tc / tw,
        "build_us": tb * 1e6,
        "probe_us": tw * 1e6,
        "capacity_plan": str(ex.cap_plan),
        "retries": ex.retries,
    }
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    return rows


def _bushy_data(n=600_000, dom=30_000, sel=0.02, seed=0):
    """Bushy tree (A ⋈ B) ⋈ ((R ⋈ S) ⋈ T): the non-root stage is the
    low-selectivity star of _lowsel_data (S covers a `sel` fraction of the
    y domain, so ~98% of the stage frontier dies at the S probe) — the
    regime where the compiled path beats the eager engine. The hybrid
    re-runs that star on the eager engine (COLT builds + host
    materialization) every call; the chain runs it compiled, with the
    output buffer squeezed by the planner's compact_output point."""
    rng = np.random.default_rng(seed)
    atoms = [
        Atom("A", ("u", "v")),
        Atom("B", ("v", "x")),
        Atom("R", ("x", "y")),
        Atom("S", ("y", "a")),
        Atom("T", ("y", "b")),
    ]
    q = Query(atoms)
    tree = BinaryPlan(
        BinaryPlan(atoms[0], atoms[1]),
        BinaryPlan(BinaryPlan(atoms[2], atoms[3]), atoms[4]),
    )
    ny = max(1, int(dom * sel))
    y_live = rng.choice(dom, ny, replace=False)
    m = n // 15
    rels = {
        "A": Relation("A", {"u": rng.integers(0, dom, m), "v": rng.integers(0, dom, m)}),
        "B": Relation("B", {"v": rng.integers(0, dom, m), "x": rng.integers(0, dom, m)}),
        "R": Relation("R", {"x": rng.integers(0, dom, n), "y": rng.integers(0, dom, n)}),
        "S": Relation("S", {"y": y_live[rng.integers(0, ny, ny)], "a": rng.integers(0, dom, ny)}),
        "T": Relation(
            "T", {"y": rng.integers(0, dom, n // 10), "b": rng.integers(0, dom, n // 10)}
        ),
    }
    return q, tree, rels


def run_bushy(repeats: int = 3, smoke: bool = False, path: str = "BENCH_join_perf.json"):
    """Part 4: eager vs PR 3 hybrid vs fully-compiled chain on a bushy plan.
    Steady state for both compiled variants (runners built once, compile
    excluded); the hybrid re-runs its eager non-root stages every call —
    that is exactly the per-query cost the chain removes. Full runs append
    bushy_* fields to the BENCH_join_perf.json record."""
    from repro.core import compiled_free_join, engine
    from repro.core.api import _stage_plans, _trie_modes

    q, tree, rels = _bushy_data(n=30_000, dom=3_000) if smoke else _bushy_data()
    stages = _stage_plans(q, tree)
    assert len(stages) == 2, "the tree must decompose into stage + root"

    # PR 3 hybrid: cached compiled root, eager stages re-run per call
    info_h = {}
    ch = compiled_free_join(q, rels, tree, agg="count", chain_stages=False, info=info_h)
    hybrid_runner = info_h["runner"]

    def hybrid_once():
        rels2 = dict(rels)
        for name, fj in stages[:-1]:
            bound, mult = engine.execute(fj, rels2, mode=_trie_modes(fj, "colt"), agg=None)
            rels2[name] = Relation(name, engine.materialize(bound, mult, fj.query.head))
        # faithful hybrid baseline: per-call in-graph builds, no trie cache
        return hybrid_runner.run_relations(rels2, reuse_tries=False)

    # fully-compiled chain: one on-device program for every stage
    info_c = {}
    cc = compiled_free_join(q, rels, tree, agg="count", info=info_c)
    chain_runner = info_c["runner"]

    # interleaved best-of-N: the three paths alternate inside each round so
    # machine drift (frequency scaling, allocator state) hits them equally
    # — sequential per-path timing swings the comparison by 30% run to run
    paths = [
        lambda: free_join(q, rels, tree, agg="count"),
        hybrid_once,
        lambda: chain_runner.run_relations(rels),
    ]
    counts = [fn() for fn in paths]  # warmup
    best = [float("inf")] * 3
    for _ in range(max(3, repeats)):
        for i, fn in enumerate(paths):
            t0 = time.perf_counter()
            counts[i] = fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    te, th, tc = best
    ce, ch2, cc2 = counts
    assert ce == ch == ch2 == cc == cc2, (ce, ch, ch2, cc, cc2)

    rows = [
        {"name": "joinperf.bushy_eager", "us": te * 1e6, "derived": f"count={ce}"},
        {"name": "joinperf.bushy_hybrid", "us": th * 1e6,
         "derived": f"speedup_vs_eager={te / th:.2f}x"},
        {"name": "joinperf.bushy_chained", "us": tc * 1e6,
         "derived": f"speedup_vs_hybrid={th / tc:.2f}x;plan={info_c['cap_plan']}"},
    ]
    if smoke:
        return rows
    record = {
        "bushy_query": "(A join B) join lowsel-star(R,S,T), 2% S selectivity",
        "bushy_count": ce,
        "bushy_eager_us": te * 1e6,
        "bushy_hybrid_us": th * 1e6,
        "bushy_chained_us": tc * 1e6,
        "bushy_chained_speedup_vs_hybrid": th / tc,
        "bushy_chain_plan": str(info_c["cap_plan"]),
        "bushy_retries": info_c["retries"],
    }
    import os

    if os.path.exists(path):
        with open(path) as f:
            full = json.load(f)
        full.update(record)
        with open(path, "w") as f:
            json.dump(full, f, indent=2)
            f.write("\n")
    return rows


def _selective_ends_chain(n=50_000, dense_dom=1_000, sel_dom=None, seed=0):
    """Chain A(a,b) B(b,c) C(c,d) D(d,e): b and d join keys as selective as
    the relations are wide (|A⋈B| ~ |A|), c dense (|B⋈C| ~ n^2/dense_dom).
    The left-deep intermediate A⋈B⋈C is ~n/dense_dom times the bushy
    stages' — the workload the enumeration exists for."""
    rng = np.random.default_rng(seed)
    sel_dom = sel_dom or n
    rels = {
        "A": Relation("A", {"a": rng.integers(0, n, n), "b": rng.integers(0, sel_dom, n)}),
        "B": Relation("B", {"b": rng.integers(0, sel_dom, n), "c": rng.integers(0, dense_dom, n)}),
        "C": Relation("C", {"c": rng.integers(0, dense_dom, n), "d": rng.integers(0, sel_dom, n)}),
        "D": Relation("D", {"d": rng.integers(0, sel_dom, n), "e": rng.integers(0, n, n)}),
    }
    q = Query(
        [Atom("A", ("a", "b")), Atom("B", ("b", "c")), Atom("C", ("c", "d")), Atom("D", ("d", "e"))]
    )
    return q, rels


def run_planner(repeats: int = 3, smoke: bool = False, path: str = "BENCH_join_perf.json"):
    """Part 5: greedy left-deep vs cost-based bushy enumeration, warm
    steady state. Both plans are chosen by the optimizer (no hand-written
    tree); full runs append plan_* fields to BENCH_join_perf.json."""
    from repro.core import compiled_free_join
    from repro.core import relcache

    q, rels = _selective_ends_chain(n=5_000, dense_dom=100) if smoke else _selective_ends_chain()
    relcache.FEEDBACK.clear()  # cold-plan comparison: estimates only
    runners, trees = {}, {}
    for name, level in (("greedy", 0), ("enumerated", 2)):
        info = {}
        compiled_free_join(
            q, rels, agg="count", options=ExecOptions(optimize_level=level), info=info
        )
        runners[name], trees[name] = info["runner"], info["plan_tree"]
    assert str(trees["greedy"]) != str(trees["enumerated"]), (
        "the enumeration found nothing beyond greedy on its showcase workload"
    )
    # interleaved best-of-N (see run_bushy): warm probe cost only
    paths = [
        lambda: runners["greedy"].run_relations(rels, reuse_tries=True),
        lambda: runners["enumerated"].run_relations(rels, reuse_tries=True),
    ]
    counts = [fn() for fn in paths]  # warmup
    assert counts[0] == counts[1], counts
    best = [float("inf")] * 2
    for _ in range(max(3, repeats)):
        for i, fn in enumerate(paths):
            t0 = time.perf_counter()
            counts[i] = fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    tg, tn = best
    rows = [
        {"name": "joinperf.plan_greedy", "us": tg * 1e6, "derived": f"count={counts[0]}"},
        {"name": "joinperf.plan_enumerated", "us": tn * 1e6,
         "derived": f"speedup_vs_greedy={tg / tn:.2f}x"},
    ]
    if smoke:
        return rows
    record = {
        "plan_query": "chain A(a,b) B(b,c) C(c,d) D(d,e), dense c, selective b/d",
        "plan_count": counts[0],
        "plan_greedy_us": tg * 1e6,
        "plan_enumerated_us": tn * 1e6,
        "plan_enumerated_speedup": tg / tn,
        "plan_greedy_tree": str(trees["greedy"]),
        "plan_enumerated_tree": str(trees["enumerated"]),
    }
    import os

    if os.path.exists(path):
        with open(path) as f:
            full = json.load(f)
        full.update(record)
        with open(path, "w") as f:
            json.dump(full, f, indent=2)
            f.write("\n")
    return rows


DIST_SCRIPT = r"""
import json, sys
import numpy as np, jax
from benchmarks.bench_join_perf import _lowsel_data
from benchmarks.common import timeit
from repro.core import binary2fj, factor
from repro.core.distributed import SpmdCounter
shards, n, dom, repeats = map(int, sys.argv[1:5])
q, rels = _lowsel_data(n=n, dom=dom)
fj = factor(binary2fj(q.atoms, q))
mesh = jax.make_mesh((shards,), ("data",))
ctr = SpmdCounter(q, rels, fj, None, mesh)  # planner capacities per shard
count = ctr()  # compile (+ any overflow growth) + 1st run
t, _ = timeit(lambda: ctr(), repeats=repeats, warmup=1)
print("DIST " + json.dumps({"us": t * 1e6, "count": count, "shards": shards,
                            "retries": ctr.retries, "cap_plan": str(ctr.cap_plan)}))
"""


def run_distributed(
    repeats: int = 3, smoke: bool = False, path: str = "BENCH_join_perf.json"
):
    """Compiled-distributed star-query rows (see module docstring, part 3).
    Each shard count runs in its own subprocess with that many fake CPU
    devices, pinned to the CPU platform so a child never reaches for an
    accelerator this process holds; full runs append spmd_* fields to the
    BENCH_join_perf.json record written by run_compiled_vs_eager."""
    import os
    import subprocess
    import sys as _sys

    n, dom = (30_000, 3_000) if smoke else (600_000, 30_000)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows, record = [], {}
    for shards in (2,) if smoke else (2, 4):
        env = {
            **os.environ,
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={shards} "
            + os.environ.get("XLA_FLAGS", ""),
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": "src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
        }
        res = subprocess.run(
            [_sys.executable, "-c", DIST_SCRIPT, str(shards), str(n), str(dom), str(repeats)],
            capture_output=True, text=True, env=env, timeout=1200, cwd=root,
        )
        out = [ln for ln in res.stdout.splitlines() if ln.startswith("DIST ")]
        assert out, res.stderr[-2000:]
        rec = json.loads(out[-1][5:])
        rows.append({
            "name": f"joinperf.spmd_star_{shards}shard", "us": rec["us"],
            "derived": f"count={rec['count']};retries={rec['retries']};plan={rec['cap_plan']}",
        })
        record[f"spmd_{shards}shard_us"] = rec["us"]
        record[f"spmd_{shards}shard_count"] = rec["count"]
        record[f"spmd_{shards}shard_retries"] = rec["retries"]
    if not smoke and os.path.exists(path):
        with open(path) as f:
            full = json.load(f)
        full.update(record)
        with open(path, "w") as f:
            json.dump(full, f, indent=2)
            f.write("\n")
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(run())
