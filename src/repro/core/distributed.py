"""Distributed Free Join: HyperCube (Shares) partitioning + SPMD execution.

The paper is single-core; the canonical way to distribute a worst-case
optimal join is the HyperCube / Shares scheme: pick per-variable share
counts p_v with prod(p_v) = P devices, view the device grid as a hypercube
indexed by (h_v(a_v) mod p_v), and send each tuple of R(x_i) to every
device whose coordinates agree on R's variables. Every device then runs the
*same local Free Join* on its fragment; results are a disjoint union
(counts: a psum). One round of communication, no intermediate shuffles —
this composes cleanly with Free Join because the local engine is unchanged.

Two execution paths share the partitioning logic:
  * host path (numpy + eager engine) — used for correctness tests;
  * SPMD path (`shard_map` + compiled engine + psum) — jit-able, lowers on
    the production mesh (see launch/dryrun.py); padded local fragments keep
    shapes static across devices.

The SPMD path is driven by the same planning stack as the local compiled
path (see core/compiled.py's shared-driver contract): spmd_count derives a
CapacityPlan from capacity.plan_capacities over *per-shard* statistics —
fragment sizes are the actual padded per-shard maxima and distinct counts
shrink by the hypercube share of each variable — reusing the query's one
Stats cache and one StaticSchedule. Inside the collective each device runs
make_executor, which reports per-node *required totals*; the psum carries
the count's 16-bit limbs (exact past 2**31, see compiled.wide_count) and a
pmax carries the needs, and the overflow-retry loop runs on
the host *outside* shard_map: grow exactly the offending node
(CapacityPlan.grow_to), recompile at the new capacity vector, re-run. No
overflow sentinel exists anywhere — spmd_count either returns the exact
(non-negative) count or raises after max_retries.

For acyclic queries hash partitioning on the first join key (shares
concentrated on one variable) recovers the classic distributed hash join as
a special case of the same code path.
"""
from __future__ import annotations

import itertools
from dataclasses import replace

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import api, engine, relcache
from repro.core.capacity import CapacityPlan, plan_capacities
from repro.core.compiled import (
    StaticTrie,
    _static_schedule,
    count_value,
    make_executor,
    overflows,
)
from repro.core.optimizer import Stats
from repro.core.plan import FreeJoinPlan
from repro.relational.npkit import mix64
from repro.relational.relation import Relation
from repro.relational.schema import Query

def _query_sig(query: Query) -> tuple:
    """Hashable structural identity of a query (its hyperedges in order)."""
    return tuple((a.alias, a.vars) for a in query.atoms)


# share assignments depend only on (hyperedges, sizes, shard count) — memoized
# process-wide so repeated queries over the same relations skip the search
_shares_cache: dict[tuple, dict[str, int]] = {}
_SHARES_CACHE_MAX = 256


def hypercube_shares(query: Query, sizes: dict[str, int], num_shards: int) -> dict[str, int]:
    """Choose shares p_v (prod = num_shards, powers of two) minimizing the
    max per-device load sum_R |R| / prod_{v in R} p_v. Exhaustive over
    exponent splits — query variable counts are tiny. Memoized on
    (hyperedges, sizes, num_shards): the assignment depends on nothing
    else, so SpmdCounter instances over the same relations share it."""
    key = (_query_sig(query), tuple(sorted(sizes.items())), num_shards)
    hit = _shares_cache.get(key)
    if hit is not None:
        return dict(hit)
    vars_ = list(query.variables)
    logp = int(np.log2(num_shards))
    assert 2**logp == num_shards, "num_shards must be a power of two"
    best, best_load = None, float("inf")

    def loads(assign: dict[str, int]) -> float:
        total = 0.0
        for a in query.atoms:
            frac = 1.0
            for v in a.vars:
                frac /= assign[v]
            total += sizes[a.alias] * frac
        return total

    for combo in itertools.combinations_with_replacement(range(len(vars_)), logp):
        assign = {v: 1 for v in vars_}
        for i in combo:
            assign[vars_[i]] *= 2
        load = loads(assign)
        if load < best_load:
            best, best_load = assign, load
    if best is None:
        # no variables to split over (e.g. a zero-variable query): every
        # shard gets the full input, the all-ones assignment
        best = {v: 1 for v in vars_}
    if len(_shares_cache) >= _SHARES_CACHE_MAX:
        _shares_cache.clear()
    _shares_cache[key] = dict(best)
    return best


def _coords(num_shards: int, shares: dict[str, int], var_order: list[str]):
    """Map shard id -> {var: coordinate} (mixed radix over shared vars)."""
    radices = [(v, shares[v]) for v in var_order if shares[v] > 1]
    out = []
    for s in range(num_shards):
        c, rem = {}, s
        for v, r in radices:
            c[v] = rem % r
            rem //= r
        out.append(c)
    return out


def partition(
    query: Query,
    relations: dict[str, Relation],
    shares: dict[str, int],
    num_shards: int,
) -> list[dict[str, Relation]]:
    """HyperCube partition: each relation row goes to every shard whose
    coordinates match the row's hashed values on the relation's vars."""
    var_order = list(query.variables)
    coords = _coords(num_shards, shares, var_order)
    shards = []
    for c in coords:
        local = {}
        for a in query.atoms:
            rel = relations[a.alias]
            mask = np.ones(rel.num_rows, dtype=bool)
            for v in a.vars:
                if shares[v] > 1:
                    hv = mix64([rel.columns[v].astype(np.int64)]) % shares[v]
                    mask &= hv == c[v]
            local[a.alias] = rel.select(mask)
        shards.append(local)
    return shards


def distributed_join_host(
    query: Query,
    relations: dict[str, Relation],
    num_shards: int,
    plan_tree=None,
    agg: str | None = None,
):
    """Reference distributed execution: partition + per-shard eager Free
    Join + union/sum. Semantically equal to single-node free_join."""
    sizes = {a.alias: relations[a.alias].num_rows for a in query.atoms}
    shares = hypercube_shares(query, sizes, num_shards)
    shards = partition(query, relations, shares, num_shards)
    if agg == "count":
        return sum(api.free_join(query, s, plan_tree, agg="count") for s in shards)
    outs = []
    for s in shards:
        bound, mult = api.free_join(query, s, plan_tree)
        outs.append(engine.materialize(bound, mult, query.head))
    return {
        v: np.concatenate([o[v] for o in outs]) if outs else np.zeros(0, np.int64)
        for v in query.head
    }


# ---------------------------------------------------------------------------
# SPMD path: shard_map(local compiled count) + psum over the mesh.
# ---------------------------------------------------------------------------


def pad_shards_to_dense(shards, query: Query):
    """Stack per-shard fragments into dense (num_shards, N_max) arrays with
    a sentinel-padded tail. Padding rows get key -1 on every column, which
    can never join (real keys are dictionary-encoded >= 0) — they flow
    through the local engine and produce zero matches by construction...
    except an all-pad relation fragment still iterates its sentinels when it
    is a pure cover, so we also hand the local engine a per-shard row count
    and mask the first node (see _mask_first)."""
    out = {}
    counts = {}
    for a in query.atoms:
        nmax = max(max(s[a.alias].num_rows for s in shards), 1)
        cols = {}
        for v in a.vars:
            arr = np.full((len(shards), nmax), -1, dtype=np.int32)
            for i, s in enumerate(shards):
                r = s[a.alias]
                arr[i, : r.num_rows] = r.columns[v].astype(np.int32)
            cols[v] = arr
        out[a.alias] = cols
        counts[a.alias] = np.array([s[a.alias].num_rows for s in shards], np.int32)
    return out, counts


def _mask_pad(cols: dict[str, dict[str, jnp.ndarray]], counts: dict[str, jnp.ndarray]):
    """Replace pad rows' keys with negative sentinels unique across *all*
    relations (a global offset per alias), so pad rows never match any probe
    and never collide with another relation's pad rows."""
    out = {}
    offset = 0
    for alias in sorted(cols):
        c = cols[alias]
        n = next(iter(c.values())).shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        pad = idx >= counts[alias]
        out[alias] = {v: jnp.where(pad, -(offset + idx) - 1, a) for v, a in c.items()}
        offset += n
    return out


# hypercube partition + dense padding + device transfer, cached across
# SpmdCounter instances over the very same Relation objects. Relation
# identity is part of the key (id per alias) and every entry is evicted by
# a weakref finalizer the moment any of its relations dies — the dense
# device fragments can neither outlive their relations nor be served to an
# unrelated object that reused a dead relation's address.
_partition_cache = relcache.KeyedCache(max_entries=8)


def _cached_partition(query: Query, relations, shares, mesh, axis: str):
    """Dense device fragments for (query, shares, mesh), reused when every
    relation object is identical to the cached entry's. Each shard's rows
    are placed straight onto its own device (sharded along `axis`), so no
    single device ever holds every fragment."""
    num_shards = mesh.shape[axis]
    rels = [relations[a.alias] for a in query.atoms]
    key = (
        _query_sig(query),
        tuple(sorted(shares.items())),
        mesh,
        axis,
        tuple(id(r) for r in rels),
    )
    hit = _partition_cache.get(key)
    if hit is not None:
        return hit
    shards = partition(query, relations, shares, num_shards)
    dense, counts = pad_shards_to_dense(shards, query)
    sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis))
    dense = jax.device_put(dense, sharding)
    counts = jax.device_put(counts, sharding)
    _partition_cache.put(key, (dense, counts), rels)
    return dense, counts


# per-shard prebuilt tries: the SPMD build program — one shard_map'd
# build_trie pass per alias, stacked along the shard axis — cached with the
# same identity discipline as the partition. Every later count executor
# (including every grow/recompile retry) takes the built tries as inputs,
# so per-shard builds run once per (relations, shares, schedule, budget)
# per process, not once per call or per retry.
_shard_trie_cache = relcache.KeyedCache(max_entries=8)


def _cached_shard_tries(
    query: Query,
    relations,
    shares,
    num_shards: int,
    dense,
    counts,
    level_ops,
    mesh,
    axis: str,
    impl: str,
    budget: int = 32,
):
    rels = [relations[a.alias] for a in query.atoms]
    key = (
        _query_sig(query),
        tuple(sorted(shares.items())),
        num_shards,
        tuple(sorted((a, lo) for a, lo in level_ops.items())),
        axis,
        impl,
        budget,
        tuple(id(r) for r in rels),
    )
    hit = _shard_trie_cache.get(key)
    if hit is not None:
        return hit
    pspec = jax.sharding.PartitionSpec(axis)
    in_specs = (
        jax.tree.map(lambda _: pspec, dense),
        jax.tree.map(lambda _: pspec, counts),
    )

    def per_shard(cols, cnts):
        cols = jax.tree.map(lambda x: x[0], cols)
        cnts = jax.tree.map(lambda x: x[0], cnts)
        cols = _mask_pad(cols, cnts)
        # sorted in-graph (jnp.lexsort): pad sentinels are negative
        tries = {a: StaticTrie(cols[a], level_ops[a], impl, budget) for a in level_ops}
        return jax.tree.map(lambda x: x[None], tries)

    built = jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=pspec,
            check_vma=False,
        )
    )(dense, counts)
    _shard_trie_cache.put(key, built, rels)
    return built


# grown capacity plans persist across SpmdCounter instances: each process
# pays the overflow retry + recompile once per (plan, relations, shards)
# and every later instance starts overflow-free (planner-derived plans
# only — manual capacities are the caller's to manage); bounded like
# _shares_cache
_cap_plan_cache: dict[tuple, CapacityPlan] = {}
_CAP_PLAN_CACHE_MAX = 256


class _ShardStats:
    """Planner statistics for one hypercube shard, derived from the global
    Stats cache without touching any column again: a fragment of R holds the
    actual padded per-shard row maximum (known after partitioning), and a
    variable sharded p_v ways keeps ~1/p_v of its distinct values."""

    def __init__(self, base: Stats, shares: dict[str, int], sizes: dict[str, int]):
        self.base = base
        self.shares = shares
        self.sizes = sizes

    def size(self, alias: str) -> int:
        return self.sizes[alias]

    def distinct(self, alias: str, var: str) -> float:
        return max(1.0, self.base.distinct(alias, var) / self.shares.get(var, 1))


def spmd_count_program(plan, capacities, schedule, mesh, axis: str, impl: str, tries):
    """The jitted SPMD count for one capacity vector: on every device of
    `mesh`, the local compiled executor over that shard's prebuilt tries,
    then a psum of the counts and a pmax of the per-node needs. `tries`
    (stacked along `axis`; arrays or ShapeDtypeStructs) fixes the input
    structure."""
    local = make_executor(plan, capacities, impl=impl, agg="count", schedule=schedule)
    pspec, rspec = jax.sharding.PartitionSpec(axis), jax.sharding.PartitionSpec()

    def per_shard(tries):
        tries = jax.tree.map(lambda x: x[0], tries)
        c, ne, nc = local(tries)
        # count by psum of its limbs (each below 2**17, so the sum over
        # shards stays exact); needs by pmax — the host retry loop sizes
        # every device's next capacities to the worst shard's need
        return jax.lax.psum(c, axis), jax.lax.pmax(ne, axis), jax.lax.pmax(nc, axis)

    return jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: pspec, tries),),
            out_specs=(rspec, rspec, rspec),
            # the probe's while_loop, bounded by each shard's own table, has
            # no replication rule; outputs are explicitly psum/pmax-reduced
            # above, so the check adds nothing here
            check_vma=False,
        )
    )


class SpmdCounter:
    """AdaptiveExecutor's distributed sibling: partition once, then run the
    shard_map'd compiled count with the host-side grow/retry loop outside
    the collective. Compiled executors are cached per capacity vector and
    the grown plan is kept, so repeated calls run overflow-free with no
    recompiles (the steady-state surface the benchmarks measure).

    Three levels persist process-wide across *instances* over the same
    relations: the share assignment (pure function of hyperedges + sizes),
    the dense device fragments (validated by relation object identity), and
    the grown planner-derived CapacityPlan — a new counter for a repeated
    query re-partitions nothing, re-learns nothing, and recompiles only if
    its capacity vector was never seen by this instance."""

    def __init__(
        self,
        query: Query,
        relations: dict[str, Relation],
        plan: FreeJoinPlan,
        capacities: list[int] | None = None,
        mesh: jax.sharding.Mesh = None,
        axis: str = "data",
        impl: str = "jnp",
        *,
        cap_plan: CapacityPlan | None = None,
        safety: float = 2.0,
        max_retries: int = 12,
    ):
        num_shards = mesh.shape[axis]
        sizes = {a.alias: relations[a.alias].num_rows for a in query.atoms}
        self.shares = hypercube_shares(query, sizes, num_shards)
        self._dense, self._counts = _cached_partition(
            query, relations, self.shares, mesh, axis
        )
        self._plan_key = None  # set only for planner-derived plans
        if cap_plan is not None:
            # reuse the schedule riding on a caller's plan (one walk per
            # query); compaction stays off under shard_map — a reused local
            # plan may carry targets, strip them so overflows() checks what
            # ran
            self.schedule = getattr(cap_plan, "schedule", None) or _static_schedule(plan)
            cap_plan = replace(cap_plan, compact_to=(None,) * len(cap_plan.capacities))
        elif capacities is not None:
            self.schedule = _static_schedule(plan)
            n = len(self.schedule)
            cap_plan = CapacityPlan(
                capacities=tuple(int(c) for c in capacities[:n]),
                compact_to=(None,) * n,
                schedule=self.schedule,
            )
        else:
            self._plan_key = (
                str(plan), _query_sig(query), tuple(sorted(sizes.items())),
                num_shards, safety,
            )
            cached = _cap_plan_cache.get(self._plan_key)
            if cached is not None:
                # a previous instance already learned (grew) this plan; skip
                # the stats pass and start overflow-free
                cap_plan = cached
                self.schedule = cached.schedule
            else:
                # per-shard sizing: padded fragment maxima + share-shrunk
                # distinct counts, same planner as the local path
                self.schedule = _static_schedule(plan)
                frag_sizes = {
                    a: int(next(iter(cols.values())).shape[1])
                    for a, cols in self._dense.items()
                }
                cap_plan = plan_capacities(
                    plan,
                    stats=_ShardStats(Stats(relations), self.shares, frag_sizes),
                    schedule=self.schedule,
                    safety=safety,
                )
                cap_plan = replace(cap_plan, compact_to=(None,) * len(cap_plan.capacities))
        self.plan = plan
        self.cap_plan = cap_plan
        self.mesh = mesh
        self.axis = axis
        self.impl = impl
        self.max_retries = max_retries
        self.retries = 0  # total overflow re-runs across calls
        # build program: per-shard tries, prebuilt once (cached across
        # instances over the same relations) — every count executor and
        # every grow/recompile retry below reuses them as plain inputs
        self._tries = _cached_shard_tries(
            query,
            relations,
            self.shares,
            num_shards,
            self._dense,
            self._counts,
            self.schedule.level_ops,
            mesh,
            axis,
            impl,
        )
        self._cache: dict[tuple, object] = {}

    @property
    def compiles(self) -> int:
        return len(self._cache)

    def _fn(self, cp: CapacityPlan):
        if cp.capacities not in self._cache:
            self._cache[cp.capacities] = spmd_count_program(
                self.plan, cp.capacities, self.schedule, self.mesh, self.axis, self.impl,
                self._tries,
            )
        return self._cache[cp.capacities]

    def __call__(self) -> int:
        cp = self.cap_plan
        for _ in range(self.max_retries + 1):
            total, ne, nc = self._fn(cp)(self._tries)
            oe, oc = overflows(cp, ne, nc)
            if not (oe.any() or oc.any()):
                self.cap_plan = cp  # steady state: keep the grown plan
                if self._plan_key is not None:
                    # ...and persist it: the next SpmdCounter over the same
                    # relations starts from the learned capacities
                    if len(_cap_plan_cache) >= _CAP_PLAN_CACHE_MAX:
                        _cap_plan_cache.clear()
                    _cap_plan_cache[self._plan_key] = cp
                return count_value(total)
            ne, nc = np.asarray(ne), np.asarray(nc)
            # compaction is off under shard_map today, but grow symmetrically
            # with AdaptiveExecutor so the two retry loops cannot diverge
            for i in np.flatnonzero(oc):
                cp = cp.grow_to(int(i), int(nc[i]), compaction=True)
            for i in np.flatnonzero(oe):
                cp = cp.grow_to(int(i), int(ne[i]))
            self.retries += 1
        raise RuntimeError(
            f"spmd frontier overflow persists after {self.max_retries} retries: {cp}"
        )


def spmd_count(
    query: Query,
    relations: dict[str, Relation],
    plan: FreeJoinPlan,
    capacities: list[int] | None = None,
    mesh: jax.sharding.Mesh = None,
    axis: str = "data",
    impl: str = "jnp",
    *,
    cap_plan: CapacityPlan | None = None,
    safety: float = 2.0,
    max_retries: int = 12,
    info: dict | None = None,
) -> int:
    """End-to-end SPMD count: hypercube partition on the host, pad to dense,
    shard over `axis`, run the compiled local engine per device, psum.

    Capacities come from the shared planning stack (see module docstring):
    by default a CapacityPlan over per-shard statistics; `capacities` (a
    manual per-node list) or `cap_plan` override the initial plan. Overflow
    is recovered by SpmdCounter's host-side retry loop — grow the offending
    node to its reported need, recompile, re-run — so the returned count is
    always exact and non-negative; no sentinel exists to leak. `info`, if
    given, receives shares, the final capacity plan, and retry/compile
    counters."""
    counter = SpmdCounter(
        query,
        relations,
        plan,
        capacities,
        mesh,
        axis,
        impl,
        cap_plan=cap_plan,
        safety=safety,
        max_retries=max_retries,
    )
    total = counter()
    if info is not None:
        info.update(
            shares=counter.shares,
            cap_plan=counter.cap_plan,
            retries=counter.retries,
            compiles=counter.compiles,
        )
    return total
