"""Compiled (static-shape) engine + distributed HyperCube joins."""
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (
    binary2fj,
    compiled_free_join,
    factor,
    free_join,
    gj_plan,
    optimize,
    to_sorted_tuples,
)
from repro.core.compiled import count_query
from repro.core.distributed import (
    distributed_join_host,
    hypercube_shares,
    spmd_count,
)
from repro.core.plan import BinaryPlan
from repro.relational.oracle import join_oracle
from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query, clover_query, triangle_query
from tests.conftest import rand_rel
from tests.test_capacity_compiled import four_cycle_query


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_compiled_count_triangle(seed, impl):
    rng = np.random.default_rng(seed)
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 8) for a in q.atoms}
    want = len(join_oracle(q, rels))
    fj = factor(binary2fj(q.atoms, q))
    got, ovf = count_query(fj, rels, [4096] * 4, impl=impl)
    assert not ovf and got == want


def test_compiled_count_gj_plan(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 8) for a in q.atoms}
    want = len(join_oracle(q, rels))
    got, ovf = count_query(gj_plan(q, ["x", "y", "z"]), rels, [4096] * 4)
    assert not ovf and got == want


def test_compiled_overflow_detected(rng):
    q = clover_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 60, 5) for a in q.atoms}
    fj = factor(binary2fj(q.atoms, q))
    _, ovf = count_query(fj, rels, [4] * 4)
    assert ovf


def test_compiled_bag_semantics():
    rels = {
        "R": Relation("R", {"x": np.array([1, 1, 1]), "a": np.array([5, 5, 7])}),
        "S": Relation("S", {"x": np.array([1, 1]), "b": np.array([9, 9])}),
    }
    q = Query([Atom("R", ("x", "a")), Atom("S", ("x", "b"))])
    fj = factor(binary2fj(q.atoms, q))
    got, ovf = count_query(fj, rels, [64] * 3)
    assert not ovf and got == 6


def test_hypercube_shares_triangle_is_cube():
    q = triangle_query()
    shares = hypercube_shares(q, {"R": 100, "S": 100, "T": 100}, 8)
    assert sorted(shares.values()) == [2, 2, 2]


def test_hypercube_shares_zero_variables():
    # regression: no exponent combos exist for a zero-variable query; the
    # all-ones assignment (every shard sees the whole input) must come back,
    # not None
    q = Query([Atom("R", ())])
    assert hypercube_shares(q, {"R": 5}, 4) == {}
    q2 = Query([Atom("R", ("x",)), Atom("S", ("x",))])
    shares = hypercube_shares(q2, {"R": 10, "S": 10}, 1)
    assert shares == {"x": 1}


def test_partition_covers_every_output(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 60, 8) for a in q.atoms}
    want = len(join_oracle(q, rels))
    got = distributed_join_host(q, rels, num_shards=8, agg="count")
    assert got == want


def test_distributed_materialized(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 6) for a in q.atoms}
    out = distributed_join_host(q, rels, num_shards=4)
    got = sorted(zip(*(out[v] for v in q.head)))
    want = join_oracle(q, rels)
    assert [tuple(map(int, t)) for t in got] == want


def test_eager_compiled_distributed_agree_on_bushy_plan(rng):
    """Sec 5.4 regime: the hijacked optimizer emits a bushy balanced tree.
    All three execution paradigms must agree on it — the unified planning
    driver serves the compiled path's stages too."""
    q = four_cycle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 50, 6) for a in q.atoms}
    bushy = optimize(q, rels, bad=True)
    assert isinstance(bushy, BinaryPlan) and isinstance(bushy.right, BinaryPlan)
    want = len(join_oracle(q, rels))
    assert free_join(q, rels, bushy, agg="count") == want
    assert compiled_free_join(q, rels, bushy, agg="count") == want
    assert distributed_join_host(q, rels, num_shards=4, plan_tree=bushy, agg="count") == want
    bound, mult = compiled_free_join(q, rels, bushy, agg=None)
    assert to_sorted_tuples((bound, mult), q.head) == join_oracle(q, rels)


# ---------------------------------------------------------------------------
# SPMD driver: planner-derived capacities + host-side overflow retry.
# A 1-shard mesh exercises the whole shard_map + psum + retry machinery on
# the single CPU device; the 8-device variant runs in the slow subprocess
# test below.
# ---------------------------------------------------------------------------


def test_spmd_count_planner_capacities(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 80, 10) for a in q.atoms}
    want = len(join_oracle(q, rels))
    mesh = jax.make_mesh((1,), ("data",))
    fj = factor(binary2fj(q.atoms, q))
    info = {}
    got = spmd_count(q, rels, fj, None, mesh, info=info)
    assert got == want
    assert info["retries"] == 0, "planner capacities should not overflow here"
    assert info["cap_plan"].schedule is not None


def test_spmd_overflow_retry_exact_count(rng):
    """An undersized initial plan must never leak a sentinel: the retry loop
    outside the collective grows the offending node to its reported need and
    the exact (non-negative) count comes back."""
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 80, 10) for a in q.atoms}
    want = free_join(q, rels, agg="count")
    mesh = jax.make_mesh((1,), ("data",))
    fj = factor(binary2fj(q.atoms, q))
    info = {}
    got = spmd_count(q, rels, fj, [16] * 4, mesh, info=info)
    assert got == want and got >= 0
    assert info["retries"] >= 1
    assert max(info["cap_plan"].capacities) > 16
    # need-based growth: a couple of retries at most, not a doubling ladder
    assert info["retries"] <= len(info["cap_plan"].capacities)


def test_spmd_count_empty_relation(rng):
    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 40, 8) for a in q.atoms}
    rels["S"] = Relation("S", {"y": np.zeros(0, np.int64), "z": np.zeros(0, np.int64)})
    mesh = jax.make_mesh((1,), ("data",))
    fj = factor(binary2fj(q.atoms, q))
    assert spmd_count(q, rels, fj, None, mesh) == 0


def test_spmd_caches_persist_across_instances(rng):
    """The hypercube partition (dense device fragments) and the grown
    CapacityPlan persist process-wide across SpmdCounter instances over the
    very same relation objects; different relation objects re-partition."""
    from repro.core.distributed import SpmdCounter

    q = triangle_query()
    rels = {a.alias: rand_rel(rng, a.alias, a.vars, 300, 8) for a in q.atoms}
    mesh = jax.make_mesh((1,), ("data",))
    fj = factor(binary2fj(q.atoms, q))
    # a tiny safety factor undersizes the planned capacities, forcing the
    # first instance to learn (grow) the plan through the retry loop
    c1 = SpmdCounter(q, rels, fj, None, mesh, safety=1e-6)
    want = free_join(q, rels, agg="count")
    assert c1() == want
    assert c1.retries >= 1, "the undersized plan must actually grow"
    # second instance: same relations -> cached fragments + the grown plan,
    # so it starts overflow-free and never re-partitions
    c2 = SpmdCounter(q, rels, fj, None, mesh, safety=1e-6)
    assert c2._dense is c1._dense, "partition must be served from the cache"
    assert c2._tries is c1._tries, "per-shard tries must be served from the cache"
    assert c2.cap_plan == c1.cap_plan, "the grown plan must persist"
    assert c2() == want
    assert c2.retries == 0, "a persisted plan re-learns nothing"
    # fresh relation objects (same content) invalidate the identity check
    rels2 = {a.alias: Relation(a.alias, dict(rels[a.alias].columns)) for a in q.atoms}
    c3 = SpmdCounter(q, rels2, fj, None, mesh, safety=1e-6)
    assert c3._dense is not c1._dense
    assert c3._tries is not c1._tries
    assert c3() == want


def test_hypercube_shares_memoized():
    from repro.core.distributed import _shares_cache

    q = triangle_query()
    sizes = {"R": 12345, "S": 23456, "T": 34567}
    first = hypercube_shares(q, sizes, 8)
    key_count = len(_shares_cache)
    again = hypercube_shares(q, sizes, 8)
    assert again == first
    assert len(_shares_cache) == key_count, "second call must hit the memo"
    # the memo hands out copies: callers mutating shares can't poison it
    again["x"] = 99
    assert hypercube_shares(q, sizes, 8) == first


SPMD_SCRIPT = r"""
import numpy as np, jax
from repro.relational.schema import triangle_query
from repro.relational.relation import Relation
from repro.relational.oracle import join_oracle
from repro.core import binary2fj, factor
from repro.core.distributed import spmd_count  # has the shard_map compat alias
rng = np.random.default_rng(0)
q = triangle_query()
rels = {a.alias: Relation(a.alias, {v: rng.integers(0, 12, 120) for v in a.vars}) for a in q.atoms}
want = len(join_oracle(q, rels))
mesh = jax.make_mesh((8,), ("data",))
fj = factor(binary2fj(q.atoms, q))
got = spmd_count(q, rels, fj, [8192] * 4, mesh)  # manual capacities
assert got == want, (got, want)
info = {}
got = spmd_count(q, rels, fj, None, mesh, info=info)  # planner capacities
assert got == want, (got, want)
assert info["retries"] == 0, info
info = {}
got = spmd_count(q, rels, fj, [32] * 4, mesh, info=info)  # undersized: retry, no sentinel
assert got == want and got >= 0, (got, want)
assert info["retries"] >= 1, info
print("SPMD_OK", got)
"""


@pytest.mark.slow
def test_spmd_count_8_devices_subprocess():
    """shard_map + psum on 8 fake CPU devices (subprocess so the fake
    device count never leaks into this test session). Slow: compiles the
    whole executor once per device mesh in a fresh process. The child is
    pinned to the CPU: its shards are fake CPU devices by design, and an
    accelerator this process holds would be out of its reach."""
    env = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": "src",
    }
    import os

    env = {**os.environ, **env}
    res = subprocess.run(
        [sys.executable, "-c", SPMD_SCRIPT], capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "SPMD_OK" in res.stdout, res.stderr[-2000:]
