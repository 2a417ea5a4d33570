"""Counts past 2**31 on every compiled surface, without x64.

Lanes carry int32 multiplicities; a count sums them wide (16-bit limbs),
so a count of 2**33 made of lanes below 2**31 is exact on the single
dispatch, the batched runner, a standing query and the SPMD psum. A lane
whose own product passes int32 is refused (CountOverflowError), never
wrapped; a standing query answers it from the eager engine instead.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiled_free_join, free_join, gj_plan, obs, relcache
from repro.core.capacity import plan_capacities
from repro.core.compiled import (
    COUNT_LIMBS,
    MULT_SAT,
    AdaptiveExecutor,
    CountOverflowError,
    _LevelOps,
    build_trie,
    count_value,
    mul_checked,
    saturating_segment_sum,
    wide_count,
)
from repro.core.plan import binary2fj, factor
from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query
from repro.serve import StandingQueryEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def star(keys: int, rows: int, arity: int = 3, extra=None):
    """R_i(x, a_i), i < arity: every key x holds `rows` rows in each, so
    the count is keys * rows**arity and one key's lane product rows**arity.
    `extra` adds G(x, f) with f = x % 2 (a filterable var)."""
    x = np.repeat(np.arange(keys), rows)
    a = np.tile(np.arange(rows), keys)
    atoms = [Atom(f"R{i}", ("x", f"a{i}")) for i in range(arity)]
    rels = {at.alias: Relation(at.alias, {"x": x.copy(), at.vars[1]: a.copy()}) for at in atoms}
    if extra:
        atoms.append(Atom("G", ("x", "f")))
        rels["G"] = Relation("G", {"x": np.arange(keys), "f": np.arange(keys) % 2})
    return Query(atoms), rels


# ---- the primitives -----------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_wide_count_is_the_exact_sum(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 70_000))
    mult = rng.integers(0, 2**31 - 1, n).astype(np.int32)
    valid = rng.random(n) < 0.8
    got = count_value(wide_count(jnp.asarray(mult), jnp.asarray(valid)))
    assert got == int(mult[valid].astype(object).sum())
    assert got > 2**31


def test_wide_count_refuses_saturated_lanes():
    mult = jnp.asarray([3, MULT_SAT, 4, MULT_SAT], jnp.int32)
    valid = jnp.asarray([True, True, True, False])
    words = np.asarray(wide_count(mult, valid))
    assert words.shape == (COUNT_LIMBS + 1,) and words[COUNT_LIMBS] == 1
    before = obs.snapshot()["counters"].get("executor.refused_lanes", 0)
    with pytest.raises(CountOverflowError):
        count_value(words)
    assert obs.snapshot()["counters"]["executor.refused_lanes"] == before + 1
    # a batch of counts reads as an int64 vector
    batch = np.stack([np.asarray(wide_count(mult, valid & (mult < 9)))] * 2)
    np.testing.assert_array_equal(count_value(batch), [7, 7])


def test_mul_checked_is_exact_or_saturated():
    """Products around 2**30, 2**31 and 2**32, and of saturated lanes: each
    is exact below 2**31 - 1 and MULT_SAT from there."""
    rng = np.random.default_rng(5)
    m = np.concatenate([rng.integers(0, 2**31 - 1, 4000), np.arange(46330, 46350),
                        np.full(50, MULT_SAT)])
    x = np.concatenate([rng.integers(0, 2**17, 4000), np.arange(46330, 46350),
                        rng.integers(0, 3, 50)])
    got = np.asarray(mul_checked(jnp.asarray(m, jnp.int32), jnp.asarray(x, jnp.int32)))
    for gm, mm, xx in zip(got, m.tolist(), x.tolist()):
        assert int(gm) == min(mm * xx, int(MULT_SAT)), (mm, xx)


@pytest.mark.parametrize("seed", range(3))
def test_saturating_segment_sum(seed):
    rng = np.random.default_rng(seed)
    n = 100_000
    groups = np.sort(rng.integers(0, 40, n)).astype(np.int32)
    groups -= groups[0]
    big = rng.random(n) < 0.001  # a few heavy rows push some groups past int32
    data = np.where(big, rng.integers(2**29, 2**31 - 1, n), rng.integers(0, 2**15, n))
    data = data.astype(np.int32)
    want = np.bincount(groups, weights=data.astype(np.float64), minlength=n).astype(object)
    want = np.array([int(w) for w in want], dtype=object)
    got = np.asarray(saturating_segment_sum(jnp.asarray(data), jnp.asarray(groups), n))
    exact = want < int(MULT_SAT)
    assert exact.sum() > 0 and (~exact).sum() > 0
    assert all(int(g) == int(w) for g, w in zip(got[exact], want[exact]))
    assert (got[~exact] == MULT_SAT).all()
    # a sum of many small rows exactly at the boundary
    ones = jnp.full(2**16, 2**15, jnp.int32)
    zeros = jnp.zeros(2**16, jnp.int32)
    assert int(saturating_segment_sum(ones, zeros, 1)[0]) == MULT_SAT
    assert int(saturating_segment_sum(ones.at[0].set(2**15 - 2), zeros, 1)[0]) == 2**31 - 2


def test_weighted_trie_weights_saturate():
    """A stage-output trie's group weight and total past int32 read
    MULT_SAT, so every lane that reads them is refused."""
    x = jnp.asarray([0, 0, 0, 1], jnp.int32)
    y = jnp.asarray([1, 2, 3, 1], jnp.int32)
    mult = jnp.asarray([2**30, 2**30, 5, 7], jnp.int32)
    lops = _LevelOps(levels=(("x",), ("y",)), probed=(True, False))
    t = build_trie({"x": x, "y": y}, lops, mult=mult)
    gids = jnp.asarray([0, 1], jnp.int32)
    np.testing.assert_array_equal(np.asarray(t.rows_under(1, gids)), [MULT_SAT, 7])
    assert int(t.total_mult) == MULT_SAT


# ---- the compiled surfaces ----------------------------------------------


def test_compiled_count_past_int32():
    q, rels = star(8, 1024)
    assert compiled_free_join(q, rels, agg="count") == 8 * 1024**3 == free_join(
        q, rels, agg="count"
    )


def test_batched_count_past_int32():
    """One dispatch of two filtered counts (f = 0 and f = 1), each 2**32."""
    q, rels = star(8, 1024, extra=True)
    fj = factor(binary2fj(q.atoms, q))
    ex = AdaptiveExecutor(
        fj, plan_capacities(fj, rels), agg="count", filter_vars=("f",), batch=2
    )
    got = ex.run_relations(rels, filter_consts=np.asarray([[0], [1]], np.int32))
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, [4 * 1024**3, 4 * 1024**3])


def test_standing_count_past_int32():
    q, rels = star(8, 1024)
    eng = StandingQueryEngine()
    sq = eng.register(q, rels, agg="count")
    assert sq.result == 8 * 1024**3
    # one more row under key 3 in R0: 1024**2 more
    eng.ingest(rels["R0"], {"x": np.asarray([3], np.int32), "a0": np.asarray([5000], np.int32)})
    live = {a: relcache.live_relation(r) for a, r in rels.items()}
    assert sq.result == free_join(q, live, agg="count") == 8 * 1024**3 + 1024**2
    assert sq.degraded_to is None and eng.degraded_refreshes == 0


def test_lane_product_past_int32_is_refused():
    """One key, three 2048-row relations: 2**33 in one lane. A plan that
    factorizes every relation under x refuses it; the optimizer's plan
    (which iterates one relation's rows) counts it exactly."""
    q, rels = star(1, 2048)
    want = 2048**3
    plan = gj_plan(q, ["x", "a0", "a1", "a2"])
    ex = AdaptiveExecutor(plan, plan_capacities(plan, rels), agg="count")
    with pytest.raises(CountOverflowError):
        ex.run_relations(rels)
    try:
        got = compiled_free_join(q, rels, agg="count")
    except CountOverflowError:
        got = None
    assert got in (want, None)


def test_standing_refusal_answers_exactly_from_eager():
    """Three unary relations of 2**16 rows on one key: every plan iterates
    one relation's rows and weighs each lane by the other two, 2**32. The
    compiled count refuses it; a standing query answers from the eager
    engine, exactly, and says so."""
    atoms = [Atom(f"U{i}", ("x",)) for i in range(3)]
    rels = {a.alias: Relation(a.alias, {"x": np.zeros(2**16, np.int64)}) for a in atoms}
    q = Query(atoms)
    with pytest.raises(CountOverflowError):
        compiled_free_join(q, rels, agg="count")
    eng = StandingQueryEngine()
    sq = eng.register(q, rels, agg="count")
    assert sq.result == 2**48
    assert sq.degraded_to == "eager" and eng.degraded_refreshes == 1


SPMD_WIDE = r"""
import numpy as np, jax
from repro.core import binary2fj, factor
from repro.core.distributed import spmd_count
from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query
assert len(jax.devices()) == 4
keys, rows = 8, 1024
x = np.repeat(np.arange(keys), rows)
a = np.tile(np.arange(rows), keys)
atoms = [Atom(f"R{i}", ("x", f"a{i}")) for i in range(3)]
rels = {t.alias: Relation(t.alias, {"x": x, t.vars[1]: a}) for t in atoms}
q = Query(atoms)
mesh = jax.make_mesh((4,), ("data",))
got = spmd_count(q, rels, factor(binary2fj(q.atoms, q)), None, mesh)
assert got == keys * rows**3, got
print("SPMD_WIDE_OK", got)
"""


def test_spmd_count_past_int32_on_4_devices():
    """The SPMD psum of a count of 2**33 over 4 virtual CPU devices (a
    subprocess, so the device count stays out of this session)."""
    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": "src",
    }
    res = subprocess.run(
        [sys.executable, "-c", SPMD_WIDE], capture_output=True, text=True, env=env,
        timeout=600, cwd=ROOT,
    )
    assert "SPMD_WIDE_OK" in res.stdout, res.stderr[-2000:]
