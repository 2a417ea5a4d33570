"""Capacity planner + adaptive compiled execution + frontier compaction."""
import jax
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import compiled_free_join, free_join, optimize, to_sorted_tuples
from repro.core.capacity import CapacityPlan, agm_bound, plan_capacities
from repro.core.compiled import (
    AdaptiveExecutor,
    count_value,
    make_executor,
    relations_to_cols,
)
from repro.core.optimizer import estimate_prefixes
from repro.core.plan import binary2fj, factor
from repro.kernels import ops, ref
from repro.relational.oracle import join_oracle
from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query, triangle_query
from tests.conftest import rand_rel

IMPLS = ["jnp", "pallas_interpret", "pallas"]


def _skip_if_unrunnable(impl):
    if impl == "pallas" and jax.default_backend() == "cpu":
        pytest.skip("compiled Pallas needs a TPU/GPU backend")


def four_cycle_query() -> Query:
    return Query(
        [Atom("R", ("x", "y")), Atom("S", ("y", "z")), Atom("T", ("z", "w")), Atom("U", ("w", "x"))]
    )


def path_query(m: int) -> Query:
    vs = [f"v{i}" for i in range(m + 1)]
    return Query([Atom(f"R{i}", (vs[i], vs[i + 1])) for i in range(m)])


def star_query(m: int) -> Query:
    return Query([Atom(f"R{i}", ("h", f"s{i}")) for i in range(m)])


# ---- end-to-end parity: no manual capacities anywhere --------------------


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("make_q", [lambda: path_query(3), lambda: star_query(3)])
def test_compiled_eager_parity_acyclic(seed, make_q):
    rng = np.random.default_rng(seed)
    q = make_q()
    assert q.is_acyclic()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 50 + 10 * seed, 7) for a in q.atoms}
    want = free_join(q, rels, agg="count")
    info = {}
    got = compiled_free_join(q, rels, agg="count", info=info)
    assert got == want
    assert info["retries"] == 0, "planner capacities should not overflow here"


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("make_q", [triangle_query, four_cycle_query])
def test_compiled_eager_parity_cyclic(seed, make_q):
    rng = np.random.default_rng(seed)
    q = make_q()
    assert not q.is_acyclic()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 60, 9) for a in q.atoms}
    want = free_join(q, rels, agg="count")
    got = compiled_free_join(q, rels, agg="count")
    assert got == want


def test_compiled_materialization_matches_oracle(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 50, 7) for a in q.atoms}
    bound, mult = compiled_free_join(q, rels, agg=None)
    assert to_sorted_tuples((bound, mult), q.head) == join_oracle(q, rels)


@pytest.mark.parametrize("empty_alias", ["R", "S", "T"])
def test_compiled_empty_relation(rng, empty_alias):
    # zero-row relations run through the executor natively: an empty trie's
    # every frontier expansion yields zero live lanes (no host-side gate)
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 8) for a in q.atoms}
    vars_ = q.atom(empty_alias).vars
    rels[empty_alias] = Relation(empty_alias, {v: np.zeros(0, np.int64) for v in vars_})
    assert free_join(q, rels, agg="count", compiled=True) == 0
    bound, mult = compiled_free_join(q, rels, agg=None)
    assert to_sorted_tuples((bound, mult), q.head) == []


def test_compiled_all_relations_empty(rng):
    q = triangle_query()
    rels = {
        a.alias: Relation(a.alias, {v: np.zeros(0, np.int64) for v in a.vars})
        for a in q.atoms
    }
    assert free_join(q, rels, agg="count", compiled=True) == 0
    bound, mult = free_join(q, rels, agg=None, compiled=True)
    assert to_sorted_tuples((bound, mult), q.head) == []


def test_compiled_bag_materialization():
    rels = {
        "R": Relation("R", {"x": np.array([1, 1, 1]), "a": np.array([5, 5, 7])}),
        "S": Relation("S", {"x": np.array([1, 1]), "b": np.array([9, 9])}),
    }
    q = Query([Atom("R", ("x", "a")), Atom("S", ("x", "b"))])
    bound, mult = compiled_free_join(q, rels, agg=None)
    assert to_sorted_tuples((bound, mult), q.head) == join_oracle(q, rels)
    assert int(np.sum(mult)) == 6


# ---- adaptive overflow recovery ------------------------------------------


def test_overflow_retry_converges_from_undersized_plan(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 6) for a in q.atoms}
    want = free_join(q, rels, agg="count")
    fj = factor(binary2fj(q.atoms, q))
    n = len(plan_capacities(fj, rels).capacities)
    # undersized by ~2-4x: a couple of doublings (= executor recompiles) fix it
    tiny = CapacityPlan(capacities=(64,) * n, compact_to=(None,) * n)
    ex = AdaptiveExecutor(fj, tiny, agg="count")
    got = ex.run_relations(rels)
    assert got == want
    assert ex.retries > 0, "a forced initial overflow must actually retry"
    assert max(ex.cap_plan.capacities) > 64
    # steady state: the grown plan is cached, a second call never re-runs
    compiles = ex.compiles
    retries = ex.retries
    assert ex.run_relations(rels) == want
    assert ex.retries == retries and ex.compiles == compiles


def test_overflow_retry_grows_only_offending_node(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 6) for a in q.atoms}
    fj = factor(binary2fj(q.atoms, q))
    good = plan_capacities(fj, rels)
    # undersize only the last node; earlier capacities must stay untouched
    caps = list(good.capacities)
    caps[-1] = 128
    ex = AdaptiveExecutor(
        fj, CapacityPlan(capacities=tuple(caps), compact_to=good.compact_to), agg="count"
    )
    assert ex.run_relations(rels) == free_join(q, rels, agg="count")
    assert ex.cap_plan.capacities[:-1] == good.capacities[:-1]
    assert ex.cap_plan.capacities[-1] > 128
    # the executor reported the node's exact required total, so the runner
    # jumps straight there: one retry, not a geometric doubling ladder
    assert ex.retries == 1


# ---- compaction ----------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
def test_compact_matches_reference(impl, rng):
    _skip_if_unrunnable(impl)
    for n, cap in [(1, 1024), (1000, 1024), (4096, 2048)]:
        valid = jnp.asarray(rng.random(n) < 0.3)
        ws, wl = ref.compact_ref(valid, cap)
        gs, gl = ops.compact_indices(valid, cap, impl=impl)
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
        assert int(gl) == int(wl)


@pytest.mark.parametrize("impl", IMPLS)
def test_compact_idempotent(impl, rng):
    """compact∘compact = compact: recompacting a compacted frontier is the
    identity on the live prefix."""
    _skip_if_unrunnable(impl)
    n, cap = 3000, 2048
    valid = jnp.asarray(rng.random(n) < 0.2)
    payload = jnp.asarray(rng.integers(0, 10**6, n).astype(np.int32))
    src1, live1 = ops.compact_indices(valid, cap, impl=impl)
    out1 = jnp.where(src1 >= 0, payload[jnp.clip(src1, 0, n - 1)], -1)
    valid1 = jnp.arange(cap) < live1
    src2, live2 = ops.compact_indices(valid1, cap, impl=impl)
    out2 = jnp.where(src2 >= 0, out1[jnp.clip(src2, 0, cap - 1)], -1)
    assert int(live2) == int(live1)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out1))


def test_executor_with_forced_compaction_matches(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 120, 40) for a in q.atoms}
    want = free_join(q, rels, agg="count")
    fj = factor(binary2fj(q.atoms, q))
    caps = [4096] * 2
    cols = relations_to_cols(fj, rels)
    plain = jax.jit(make_executor(fj, caps))(cols)
    squeezed = jax.jit(make_executor(fj, caps, compact_to=[1024, None]))(cols)
    assert count_value(plain[0]) == want == count_value(squeezed[0])
    # executors report *required totals* per node, not overflow bits
    assert (np.asarray(squeezed[1]) <= np.array(caps)).all()
    assert np.asarray(squeezed[2])[0] <= 1024


def test_midnode_compaction_between_probes(rng):
    """Factored star plan: node 0 is [R(x,y), S(y), T(y)]. Compacting right
    after the selective S probe must not change the count, and the planner
    must actually schedule a mid-node compact point on low selectivity."""
    q = Query([Atom("R", ("x", "y")), Atom("S", ("y", "a")), Atom("T", ("y", "b"))])
    n, dom = 400, 40
    y_live = rng.choice(dom, 3, replace=False)  # S kills ~92% of lanes
    rels = {
        "R": rand_rel(rng, "R", ("x", "y"), n, dom),
        "S": Relation("S", {"y": y_live[rng.integers(0, 3, 6)], "a": rng.integers(0, dom, 6)}),
        "T": rand_rel(rng, "T", ("y", "b"), n // 4, dom),
    }
    want = free_join(q, rels, agg="count")
    fj = factor(binary2fj(q.atoms, q))
    assert [sa.alias for sa in fj.nodes[0]] == ["R", "S", "T"]
    cp = plan_capacities(fj, rels, block=128)  # tiny data: sub-1024 blocks
    assert cp.compact_to[0] is not None and cp.compact_probe[0] == 1
    cols = relations_to_cols(fj, rels)
    for cpr in [None, cp.compact_probe]:  # after-node vs mid-node
        out = jax.jit(make_executor(fj, cp.capacities, compact_to=cp.compact_to,
                                    compact_probe=cpr))(cols)
        assert count_value(out[0]) == want
        assert (np.asarray(out[1]) <= np.array(cp.capacities)).all()
        assert np.asarray(out[2])[0] <= cp.compact_to[0]
    ex = AdaptiveExecutor(fj, cp, agg="count")
    assert ex.run_relations(rels) == want


def test_compaction_overflow_detected_and_recovered(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 60, 10) for a in q.atoms}
    fj = factor(binary2fj(q.atoms, q))
    # ample expand buffer, absurdly small compaction target -> compact overflow
    cp = CapacityPlan(capacities=(1024, 1024), compact_to=(16, None))
    cols = relations_to_cols(fj, rels)
    out = jax.jit(make_executor(fj, cp.capacities, compact_to=cp.compact_to))(cols)
    assert np.asarray(out[2])[0] > 16, "compaction overflow must be reported as the live need"
    ex = AdaptiveExecutor(fj, cp, agg="count")
    assert ex.run_relations(rels) == free_join(q, rels, agg="count")
    assert ex.retries > 0


# ---- planner -------------------------------------------------------------


def test_agm_bound_triangle_exact():
    edges = {"R": ("x", "y"), "S": ("y", "z"), "T": ("z", "x")}
    n = 500.0
    assert agm_bound(edges, {a: n for a in edges}) == pytest.approx(n**1.5, rel=1e-6)


def test_capacity_plan_block_aligned_and_agm_capped(rng):
    q = triangle_query()
    # dense small domain: estimates explode past the AGM bound
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 400, 4) for a in q.atoms}
    cp = plan_capacities(factor(binary2fj(q.atoms, q)), rels, block=1024)
    assert all(c % 1024 == 0 for c in cp.capacities)
    for cap, bound in zip(cp.capacities, cp.agm):
        assert cap <= max(1024, int(np.ceil(bound / 1024)) * 1024)
    ests = cp.estimates
    assert len(ests) == len(cp.capacities)
    assert all(e.after <= e.expand for e in ests)


def test_estimates_track_truth_within_order_of_magnitude(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 200, 20) for a in q.atoms}
    fj = factor(binary2fj(q.atoms, q))
    truth = free_join(q, rels, agg="count")
    est = estimate_prefixes(fj, rels)[-1].after
    assert truth / 50 <= est <= truth * 50


# ---- shared planning pass -------------------------------------------------


def test_planning_pass_host_work(rng, monkeypatch):
    """Greedy planning (optimize_level=0) computes one Stats cache and one
    StaticSchedule per query: exactly one np.unique per referenced column (6
    for the triangle) and one _static_schedule call across optimize ->
    plan_capacities -> estimate_prefixes -> make_executor. The enumerating
    default additionally schedules each device-costed finalist on the COLD
    call (bounded by the optimizer's `keep`), reuses the same Stats cache
    (zero extra np.unique), and a warm repeat — pinned choice, cached runner
    — does zero planning host work of either kind."""
    import repro.core.compiled as compiled_mod
    from repro.core import ExecOptions

    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 8) for a in q.atoms}
    want = free_join(q, rels, agg="count")

    uniq, sched = [0], [0]
    orig_unique, orig_sched = np.unique, compiled_mod._static_schedule
    monkeypatch.setattr(
        np, "unique", lambda *a, **k: (uniq.__setitem__(0, uniq[0] + 1), orig_unique(*a, **k))[1]
    )
    monkeypatch.setattr(
        compiled_mod,
        "_static_schedule",
        lambda p: (sched.__setitem__(0, sched[0] + 1), orig_sched(p))[1],
    )
    greedy = ExecOptions(optimize_level=0)
    assert compiled_free_join(q, rels, agg="count", options=greedy) == want
    assert uniq[0] == 6, f"one np.unique per column, got {uniq[0]}"
    assert sched[0] == 1, f"one schedule computation per query, got {sched[0]}"

    # cold enumerating call: per-finalist costing, same Stats cache
    assert compiled_free_join(q, rels, agg="count") == want
    cold_uniq, cold_sched = uniq[0], sched[0]
    assert cold_uniq == 6, f"Stats cache shared across levels, got {cold_uniq}"
    assert cold_sched <= 1 + 2 * 3 + 2, f"finalist costing unbounded: {cold_sched}"

    # warm repeat: choice pinned, runner cached — zero host planning
    assert compiled_free_join(q, rels, agg="count") == want
    assert (uniq[0], sched[0]) == (cold_uniq, cold_sched), "warm call re-planned"


def test_capacity_plan_carries_schedule(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 8) for a in q.atoms}
    fj = factor(binary2fj(q.atoms, q))
    cp = plan_capacities(fj, rels)
    assert cp.schedule is not None and len(cp.schedule) == len(cp.capacities)
    ex = AdaptiveExecutor(fj, cp, agg="count")
    assert ex.schedule is cp.schedule  # reused, not recomputed
    # grow() / grow_to() keep the schedule on the derived plans
    assert cp.grow(0).schedule is cp.schedule
    assert cp.grow_to(0, 10**6).schedule is cp.schedule


# ---- optimizer degenerate case (regression) ------------------------------


def test_optimize_bad_single_atom_returns_atom(rng):
    q = Query([Atom("R", ("x", "y"))])
    rels = {"R": rand_rel(rng, "R", ("x", "y"), 25, 5)}
    tree = optimize(q, rels, bad=True)
    assert isinstance(tree, Atom) and tree.alias == "R"
    assert free_join(q, rels, tree, agg="count") == 25
    assert free_join(q, rels, optimize(q, rels), agg="count") == 25
    assert compiled_free_join(q, rels, agg="count") == 25


# ---- kernel choice per backend ------------------------------------------


def test_exec_options_refuse_pallas_on_tpu(monkeypatch):
    """impl="pallas" fails at option construction on a TPU backend, with
    the reason, instead of deep inside a trace (or silently running jnp)."""
    from repro.core.api import ExecOptions

    assert ExecOptions(impl="pallas").impl == "pallas"  # CPU: left to the caller
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="not yet been written to compile for a TPU"):
        ExecOptions(impl="pallas")
    assert ExecOptions(impl="jnp").impl == "jnp"
    assert ExecOptions(impl="pallas_interpret").impl == "pallas_interpret"
