"""Static-shape Free Join: the jit/shard_map-able TPU path.

The eager engine (engine.py) is the paper-faithful reproduction; this module
re-expresses the same plan execution with fully static shapes so it lowers
under jit on a device mesh. Since PR 5 the compiled path is split into two
programs with an explicit contract between them:

* The BUILD program (build_trie / StaticTrie) turns a relation's columns
  into a column-oriented lazy trie: one sort over the consumed level vars +
  boundary flags + segment sums — all arrays keep the base relation's
  static length N (group counts are dynamic *values*, never dynamic
  *shapes*). COLT's "build only what the plan consumes" survives statically
  twice over: only levels the plan probes get hash tables, and a relation
  that is only iterated at a single level skips the build entirely. Who
  sorts depends on who dispatches the build. A build dispatched from the
  host (every cached, rebuilt or merged trie, and a standing query's
  stage tries) takes its row order and its tables' slot orders from
  ops.lex_order, one XLA sort program per size bucket; its own programs
  hold no sort (host_sorted_trie). A build traced into a larger program
  (an executor's stage-output or raw-column trie, an SPMD shard) sorts
  inline (jnp.lexsort), and on a TPU that program pays 20-60 s more
  compile per sort at a few 100k rows and up, once per shape, kept by the
  compile cache.
  A StaticTrie is a registered pytree, so a prebuilt trie crosses the jit
  boundary as a plain *input* of device arrays.

* The PROBE program (make_executor / make_chain_executor) takes tries —
  prebuilt pytrees or raw column dicts, per alias — and runs the plan over
  a capacity-bounded frontier. A raw dict is built in-graph (the cold
  path, and the only path for weighted stage buffers, which exist only
  mid-chain); a prebuilt trie contributes zero build work to the call.
  Iteration is expand_counted (prefix-sum + binary-search addressing);
  probing is the hash_probe kernel; predicted-dead frontiers are compacted
  (kernels/compact.py). Bag semantics via a mult column; factorized
  counting decided statically from the plan.

* The cross-call TRIE CACHE (TrieCache / TRIE_CACHE) amortizes builds
  across calls, the COLT move that makes steady-state serving pay probe
  cost only. It is keyed by relation identity (weakref registry — entries
  die with their relations, see core/relcache.py) + level layout + budget,
  revalidated per column by host-array identity, and lazy per level: a
  schedule that probes a level the cached build skipped adds exactly that
  level's table; a layout whose level vars form the same sequence as a
  cached one's (((x,), (y,)) and ((x, y),)) reuses the cached sort order
  and pays no sort. Weighted (stage-output) tries are never cached: their
  rows are padded frontier lanes of one specific run, so reuse across runs
  would serve stale intermediates.

* Since PR 9 the cache has a DELTA path for relations mutated through
  core/relcache.py's append/delete API, replacing rebuild-on-any-change.
  A mutating relation's trie is padded to a power-of-two capacity bucket
  (_bucket), pad rows carrying PAD_KEY keys and multiplicity 0 so they
  sort to the tail and weigh nothing. An append sorts ONLY the delta
  (ops.lex_order, from the host) and splices the sorted run into the
  cached level buffers with a rank-merge (_merge_append_jit):
  lex_searchsorted ranks each delta row against the old sorted order,
  position arithmetic scatters both runs into the new order, and the trie
  is rebuilt over that order — zero sort passes over old rows. The real
  row count crosses the jit boundary as a device scalar, so same-bucket
  appends reuse one compiled merge program. A delete tombstones rows in place
  (_retire_rows_jit zeroes their weights and refreshes group weights);
  when live/total drops below the state's compact_ratio, relcache
  compacts and the next access pays one honest rebuild. Counters
  (delta_merges, tombstone_refreshes) make the contract testable:
  appends move delta_merges while builds stands still.

Bushy plans run fully compiled (Sec 2.2): make_chain_executor strings every
stage's executor into ONE on-device program — a non-root stage runs with
agg=None, its output columns stay on device as a padded buffer (invalid
lanes stamped PAD_KEY with multiplicity 0), and the next stage builds a
*weighted* StaticTrie straight from that buffer, in-graph.

The shared-driver contract (one planning pass serves the local *and* the
distributed compiled paths — api.compiled_free_join and
distributed.spmd_count are both thin drivers over the same stack):

* The driver builds one optimizer.Stats cache and one StaticSchedule per
  stage and threads them through optimize -> capacity.plan_chain_capacities
  -> optimizer.estimate_prefixes -> make_executor. On a warm call the
  costly parts of that pass disappear: distinct counts come from the
  weakref registry (zero np.unique), AGM bounds from a memo, and the whole
  runner — capacity plan and compiled executors — from api._runner_cache.
  Plan *enumeration* (optimize's greedy search, pure host arithmetic over
  cached stats) still runs per call, because the runner key is derived
  from the chosen plan.
* make_executor builds the jit-able executor for one capacity vector.
  Buffer pressure is reported per node as *required totals*: agg="count"
  returns (count, need_expand, need_compact); agg=None returns (bound
  columns, valid mask, mult, need_expand, need_compact). Node i overflowed
  iff the need exceeds its capacity, and the need is the exact capacity the
  retry loop should jump to. A count is a small int32 vector of 16-bit
  limbs (wide_count), exact up to 2**63 - 1 without x64, which the host
  reads with count_value; a lane multiplicity that would pass int32 is
  refused (MULT_SAT), and a result it reaches raises CountOverflowError.
* AdaptiveExecutor drives the whole chain in an overflow-retry loop (grow
  exactly the offending node straight to its reported need; tighten=True
  also shrinks >2x-oversized buffers to measured needs once), caching one
  compiled executor per capacity-vector chain. run_relations is the warm
  serving surface: device uploads, built tries, and planning statistics all
  come from the registry, so a retry or tighten re-run recompiles the probe
  program but never rebuilds a trie.
* Zero-row relations are handled natively: an empty relation builds a
  StaticTrie whose every frontier expansion yields zero live lanes and
  whose probes match nothing, so drivers need no host-side empty gate.

make_count_fn/count_query keep the original count-only surface (manual
capacities, scalar overflow bit) for benchmarks and dry runs.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults, membudget, obs, relcache
from repro.core.plan import FreeJoinPlan
from repro.kernels import ops, scan

# Key stamped on the pad (invalid) lanes of a materialized stage buffer.
# Real join keys are dictionary-encoded int32 >= 0 and never reach int32
# max, so pad rows lose every probe immediately; correctness does not rest
# on that (their multiplicity is 0), it only keeps dead lanes short-lived.
PAD_KEY = np.int32(2**31 - 1)

# Multiplicities ride the frontier as int32 (JAX runs without x64). A lane
# whose product would reach 2**31 - 1 is refused instead of wrapped: it
# reads MULT_SAT from then on, and a result that a refused lane reaches
# raises CountOverflowError on the host. A count is summed wide (wide_count).
MULT_SAT = np.int32(2**31 - 1)
COUNT_LIMBS = 4  # 16-bit limbs of a wide count: exact up to 2**63 - 1
_FOLD = 1 << 14  # values per partial sum: 2**14 limbs below 2**17 stay int32
_SEG_BLOCK = 1 << 15  # rows per block of a saturating segment sum


class CountOverflowError(OverflowError):
    """A multiplicity or a count the int32 lanes cannot hold exactly: some
    lane's product reached 2**31 - 1 and that lane reaches the result."""


def mul_checked(m, x):
    """m * x for nonnegative int32 lanes, saturating: a product of
    2**31 - 1 or more reads MULT_SAT, and a MULT_SAT lane stays MULT_SAT
    (for x > 0). A zero factor gives an exact zero. No division (a TPU
    compiles an int32 divide slowly): the float32 product is within a
    relative 2**-22 of the true one, so it decides every product below
    2**30 or from 2**31.5 up; between, the true product is below 2**32, and
    the int32 product has wrapped negative exactly when it reached 2**31."""
    x = x.astype(jnp.int32)
    p = m * x
    pf = m.astype(jnp.float32) * x.astype(jnp.float32)
    over = (pf >= 2.0**31.5) | ((pf >= 2.0**30) & ((p < 0) | (p == MULT_SAT)))
    return jnp.where(over, MULT_SAT, p)


def saturating_segment_sum(data, segment_ids, num_segments: int):
    """segment_sum of nonnegative int32 `data` over non-decreasing
    `segment_ids`, exact below MULT_SAT and MULT_SAT at or above it, where
    an int32 sum would wrap. Rows are cut into blocks of _SEG_BLOCK, so each
    (segment, block) pair sums its 16-bit halves without wrapping; the pairs'
    carried halves then sum per segment, the high half in float32 as well:
    a float32 sum of integers reaches 2**15 exactly when the true sum does
    (below 2**24 every partial sum is exact, in any order)."""
    n = data.shape[0]
    if n == 0:
        return jnp.zeros(num_segments, jnp.int32)
    npair = num_segments + (n - 1) // _SEG_BLOCK + 1
    # (segment, block) is sorted along the rows, and so is its sum
    pair = segment_ids + jnp.arange(n, dtype=jnp.int32) // _SEG_BLOCK
    hi = _sorted_segment_sum(data >> 16, pair, npair)  # < 2**30
    lo = _sorted_segment_sum(data & 0xFFFF, pair, npair)  # < 2**31
    h, l = hi + (lo >> 16), lo & 0xFFFF
    # each pair's segment; pair ids no row took weigh 0 and join the last
    # segment before them, which keeps the ids sorted
    owner = jax.ops.segment_max(segment_ids, pair, npair, indices_are_sorted=True)
    owner = jnp.maximum(scan.cummax(owner), 0)
    big = _sorted_segment_sum(h.astype(jnp.float32), owner, num_segments) >= 2.0**15
    h = _sorted_segment_sum(h, owner, num_segments)  # exact unless `big`
    l = _sorted_segment_sum(l, owner, num_segments)  # < 2**31 while n < 2**30
    big = big | (l > (MULT_SAT - 1) - (jnp.clip(h, 0, 2**15 - 1) << 16))
    return jnp.where(big, MULT_SAT, (h << 16) + l)


def _fold_limbs(limbs: list) -> list:
    """One step of a wide sum: each limb's values (< 2**17) summed in
    groups of _FOLD, then the carries moved one limb up."""
    pad = (-limbs[0].shape[0]) % _FOLD
    out, carry = [], 0
    for limb in limbs:
        s = jnp.pad(limb, (0, pad)).reshape(-1, _FOLD).sum(axis=1)
        out.append((s & 0xFFFF) + carry)
        carry = s >> 16
    return out + [carry]


def wide_count(mult, valid) -> jnp.ndarray:
    """The exact sum of `mult` over the valid lanes: a (COUNT_LIMBS + 1,)
    int32 vector, COUNT_LIMBS little-endian 16-bit limbs (each below 2**17,
    so a psum over shards cannot wrap) and the number of refused lanes
    (valid lanes at MULT_SAT), which the sum leaves out. count_value reads
    it on the host."""
    assert mult.shape[0] < 2**31, "lane count past int32"
    m = jnp.where(valid, mult, 0)
    if m.shape[0] == 0:
        m = jnp.zeros(1, jnp.int32)
    refused = jnp.sum(m == MULT_SAT, dtype=jnp.int32)
    m = jnp.where(m == MULT_SAT, 0, m)
    limbs = [m & 0xFFFF, m >> 16]
    while limbs[0].shape[0] > 1:
        limbs = _fold_limbs(limbs)
    # total < 2**62, so limbs past COUNT_LIMBS hold zero
    limbs = (limbs + [jnp.zeros(1, jnp.int32)] * COUNT_LIMBS)[:COUNT_LIMBS]
    return jnp.concatenate([jnp.concatenate(limbs), refused[None]])


def count_value(words):
    """A wide count read back on the host (see wide_count) as a Python int,
    or a (B,) int64 array for a batch of them; raises CountOverflowError
    when a refused lane reached it (and counts those lanes in the counter
    `executor.refused_lanes`)."""
    w = np.asarray(words, np.int64)
    refused = int(w[..., COUNT_LIMBS].sum())
    if refused:
        obs.count("executor.refused_lanes", refused)
        raise CountOverflowError(
            f"{refused} lanes' multiplicity reached 2**31 - 1; the count would wrap"
        )
    total = sum(w[..., k] << (16 * k) for k in range(COUNT_LIMBS))
    return int(total) if w.ndim == 1 else np.asarray(total, np.int64)


@dataclass(frozen=True)
class _LevelOps:
    """Static decisions for one atom: which levels are probed/iterated."""

    levels: tuple[tuple[str, ...], ...]
    probed: tuple[bool, ...]  # per level: consumed by probe?


@dataclass(frozen=True)
class StaticSchedule:
    """One static walk of a plan, computed once per query and threaded
    through the whole driver stack (planner, estimator, executor builds).
    entries[i] = (node index, cover subatom, probe subatoms); level_ops maps
    alias -> per-level probe/iterate decisions."""

    entries: tuple
    level_ops: dict

    def __len__(self):
        return len(self.entries)

    def __iter__(self):
        return iter(self.entries)


def _static_schedule(plan: FreeJoinPlan) -> StaticSchedule:
    """Walk the plan once, statically: per node pick the cover (first listed
    — plans arrive factored), mark each atom level probe/iterate."""
    parts = plan.partitions()
    consumed: dict[str, int] = {a: 0 for a in parts}
    probed: dict[str, list[bool]] = {a: [False] * len(parts[a]) for a in parts}
    schedule = []
    for k, node in enumerate(plan.nodes):
        subs = [sa for sa in node if sa.vars]
        if not subs:
            continue
        covers = [sa for sa in plan.covers(k) if sa.vars and any(sa is s for s in subs)]
        cover = covers[0]
        probes = tuple(sa for sa in subs if sa is not cover)
        schedule.append((k, cover, probes))
        for sa in probes:
            probed[sa.alias][consumed[sa.alias]] = True
            consumed[sa.alias] += 1
        consumed[cover.alias] += 1
    level_ops = {a: _LevelOps(tuple(parts[a]), tuple(probed[a])) for a in parts}
    return StaticSchedule(entries=tuple(schedule), level_ops=level_ops)


class StaticTrie:
    """Sort-based trie with static shapes (see module docstring).

    Constructing one IS the build program; a built instance is a registered
    pytree of device arrays, so it can be returned from a jit'd build and
    fed to a jit'd probe program as an ordinary input. `order`, when given,
    is the rows' lexicographic order over the level vars, already sorted by
    the caller (ops.lex_order, a delta merge, a cached trie's order): the
    build then pays no sort. Without it the build sorts in-graph
    (jnp.lexsort).
    tables=False leaves the probed levels' hash tables to the caller.

    `mult` (optional) marks a *weighted* trie built from another stage's
    padded output buffer: row i carries multiplicity mult[i] >= 0, and rows
    with mult 0 are padding (dead executor lanes) that must contribute
    nothing. Weighted tries keep two per-group aggregates — physical row
    counts (for last-level enumeration addressing) and mult sums (for
    factorized counting and bag multiplicity) — and the executor folds the
    per-row mult in (and kills mult-0 lanes) whenever it enumerates physical
    rows. Mult sums saturate at MULT_SAT (saturating_segment_sum), so a
    group too heavy for int32 refuses the lanes that read it."""

    def __init__(
        self,
        cols: dict[str, jnp.ndarray],
        lops: _LevelOps,
        impl: str,
        budget: int = 32,
        mult: jnp.ndarray | None = None,
        order: jnp.ndarray | None = None,
        tables: bool = True,
    ):
        self.impl = impl
        self.budget = budget
        self.lops = lops
        self.L = len(lops.levels)
        self.levels = lops.levels
        some = next(iter(cols.values()))
        self.empty = some.shape[0] == 0
        if self.empty:
            # zero-row relation: keep one sentinel row so every downstream
            # gather has a real operand; iter_counts/rows_under/probe below
            # force zero live lanes, so the sentinel is never observable
            cols = {k: jnp.full(1, -1, jnp.int32) for k in cols}
            some = next(iter(cols.values()))
            mult = None
        n = some.shape[0]
        self.n = n
        self.cols = {k: v.astype(jnp.int32) for k, v in cols.items()}
        self.mult_col = None if mult is None else mult.astype(jnp.int32)
        self.total_mult = None
        if mult is not None:
            zeros = jnp.zeros(n, jnp.int32)
            self.total_mult = saturating_segment_sum(self.mult_col, zeros, 1)[0]
        self.trivial = self.L == 1 and not lops.probed[0]
        self.order = None
        self.sorted_cols = None
        self.g = self.kpos = None
        self.child_base = self.child_counts = self.row_count = None
        self.row_weight = self.tables = None
        if self.trivial:  # pure cover: iterate the base table, zero build
            return
        all_vars = [v for lv in lops.levels for v in lv]
        if self.empty:
            order = jnp.zeros(1, jnp.int32)
        elif order is None:
            order = jnp.lexsort(tuple(self.cols[v] for v in reversed(all_vars)))
        self.order = order.astype(jnp.int32)
        sc = {v: self.cols[v][order] for v in all_vars}
        self.sorted_cols = sc
        sm = None if self.mult_col is None else self.mult_col[order]
        idx = jnp.arange(n, dtype=jnp.int32)
        # depth-d group ids for d = 0..L, flags for d = 1..L
        self.g = [jnp.zeros(n, jnp.int32)]  # g[0] = root
        self.kpos = [jnp.zeros(1, jnp.int32)]  # first position of each group
        flag = jnp.zeros(n, dtype=bool)
        self.child_base, self.child_counts, self.row_count, self.tables = [], [], [], []
        self.row_weight = []
        for d, lv in enumerate(lops.levels):
            diff = jnp.zeros(n, dtype=bool).at[0].set(True)
            for v in lv:
                diff = diff.at[1:].set(diff[1:] | (sc[v][1:] != sc[v][:-1]))
            flag = flag | diff
            flag = flag.at[0].set(True)
            gd1 = (scan.cumsum(flag.astype(jnp.int32)) - 1).astype(jnp.int32)  # g[d+1]
            # group ids are non-decreasing along the sorted rows, so every
            # segment reduction below is a sorted scatter: the TPU compiler
            # sorts the indices of an unsorted one, at a large compile cost
            # children of each depth-d group (counts over depth-(d+1) firsts)
            ccnt = _sorted_segment_sum(flag.astype(jnp.int32), self.g[d], n)
            cbase = scan.cumsum(ccnt) - ccnt
            # first position of each group (0 past the last group)
            kp = jnp.full(n, n, jnp.int32).at[gd1].min(idx, indices_are_sorted=True)
            kp = jnp.where(kp < n, kp, 0)
            rcnt = _sorted_segment_sum(jnp.ones(n, jnp.int32), gd1, n)
            self.g.append(gd1)
            self.kpos.append(kp)
            self.child_base.append(cbase.astype(jnp.int32))
            self.child_counts.append(ccnt.astype(jnp.int32))
            self.row_count.append(rcnt)
            if sm is not None:
                self.row_weight.append(saturating_segment_sum(sm, gd1, n))
            # probed levels get their hash table; one shared construction
            # with the lazy path (build_level_table), so eagerly- and
            # lazily-built tables can never drift
            probed = lops.probed[d] and tables
            self.tables.append(self.build_level_table(d, budget) if probed else None)

    # -- pytree protocol: a built trie crosses jit boundaries as an input --

    def tree_flatten(self):
        children = (
            self.cols,
            self.mult_col,
            self.total_mult,
            self.order,
            self.sorted_cols,
            self.g,
            self.kpos,
            self.child_base,
            self.child_counts,
            self.row_count,
            self.row_weight,
            self.tables,
        )
        aux = (self.lops, self.impl, self.budget, self.n, self.empty)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        t = object.__new__(cls)
        (
            t.cols,
            t.mult_col,
            t.total_mult,
            t.order,
            t.sorted_cols,
            t.g,
            t.kpos,
            t.child_base,
            t.child_counts,
            t.row_count,
            t.row_weight,
            t.tables,
        ) = children
        t.lops, t.impl, t.budget, t.n, t.empty = aux
        t.levels = t.lops.levels
        t.L = len(t.levels)
        t.trivial = t.L == 1 and not t.lops.probed[0]
        return t

    def build_level_table(self, d: int, budget: int | None = None, *, host_sort=False):
        """Build the depth-d probe table on an already-sorted trie — the
        lazy-COLT path for a schedule that probes a level the cached build
        skipped. Device work is exactly one table build; the sort and the
        group structure are reused. host_sort: see ops.build_table."""
        assert not self.trivial and self.g is not None
        lv = self.levels[d]
        n = self.n
        idx = jnp.arange(n, dtype=jnp.int32)
        gd1 = self.g[d + 1]
        flag = jnp.zeros(n, dtype=bool).at[0].set(True)
        flag = flag.at[1:].set(gd1[1:] != gd1[:-1])
        parent = jnp.where(flag, self.g[d], -idx - 2)
        key_rows = jnp.stack(
            [parent] + [jnp.where(flag, self.sorted_cols[v], 0) for v in lv], axis=1
        )
        return ops.build_table(key_rows, budget=budget or self.budget, host_sort=host_sort)

    def table_view(self, probed: tuple[bool, ...]) -> "StaticTrie":
        """A shallow view sharing every array, exposing tables only where
        `probed` asks — so the executor's input pytree structure depends
        only on the schedule, not on how many tables the cached build has
        accumulated."""
        if self.trivial:
            return self
        children, aux = self.tree_flatten()
        lops, impl, budget, n, empty = aux
        aux = (replace(lops, probed=tuple(probed)), impl, budget, n, empty)
        view = self.tree_unflatten(aux, children)
        view.tables = [t if p else None for t, p in zip(self.tables, probed)]
        return view

    # depth-d group sizes (weighted by mult for stage tries): drives
    # factorized count and last-level probe multiplicity
    def rows_under(self, d: int, gids: jnp.ndarray) -> jnp.ndarray:
        if self.empty:
            return jnp.zeros(gids.shape, jnp.int32)
        if self.trivial or d == 0:
            if self.total_mult is not None:
                return jnp.broadcast_to(self.total_mult, gids.shape)
            return jnp.full(gids.shape, self.n, jnp.int32)
        if self.mult_col is not None:
            return self.row_weight[d - 1][gids]
        return self.row_count[d - 1][gids]

    # physical depth-d group sizes: addressing for last-level enumeration
    def _phys_rows(self, d: int, gids: jnp.ndarray) -> jnp.ndarray:
        if self.trivial or d == 0:
            return jnp.full(gids.shape, self.n, jnp.int32)
        return self.row_count[d - 1][gids]

    def probe(self, d: int, gids, key_cols):
        if self.empty:  # nothing to match: kill every probing lane
            return jnp.full(gids.shape, -1, jnp.int32)
        q = jnp.stack([gids.astype(jnp.int32)] + [c.astype(jnp.int32) for c in key_cols], axis=1)
        p = ops.probe(self.tables[d], q, impl=self.impl)
        child = self.g[d + 1][jnp.clip(p, 0, self.n - 1)]
        return jnp.where(p >= 0, child, -1)

    def iter_counts(self, d: int, gids, last: bool):
        """(base, counts) for expand_counted at level d from groups `gids`.
        last=True enumerates rows; otherwise enumerates child groups."""
        z = jnp.zeros(gids.shape, jnp.int32)
        if self.empty:  # every expansion yields zero live lanes
            return z, z
        if self.trivial:
            return z, jnp.full(gids.shape, self.n, jnp.int32)
        if last:
            base = (
                self.kpos[d][jnp.clip(gids, 0, self.n - 1)]
                if d > 0
                else jnp.zeros(gids.shape, jnp.int32)
            )
            counts = self._phys_rows(d, gids)
            return base, counts
        return self.child_base[d][gids], self.child_counts[d][gids]

    def bind_iter(self, d: int, members, last: bool):
        """Column values bound by iterating; members from expand_counted.
        Returns (cols list in level-var order, new_gids or None)."""
        lv = self.levels[d]
        if self.trivial:
            return [self.cols[v][members] for v in lv], None
        if last:
            rows = self.order[members]
            return [self.cols[v][rows] for v in lv], self.g[d + 1][members]
        kp = self.kpos[d + 1][members]
        return [self.sorted_cols[v][kp] for v in lv], members

    def iter_mult(self, members) -> jnp.ndarray | None:
        """Per-row multiplicity of the physical rows enumerated by a
        last-level bind_iter (None for unweighted tries: each row counts 1).
        A zero marks a pad row — the executor kills that lane."""
        if self.mult_col is None:
            return None
        rows = members if self.trivial else self.order[members]
        return self.mult_col[rows]


jax.tree_util.register_pytree_node(
    StaticTrie, StaticTrie.tree_flatten, StaticTrie.tree_unflatten
)


def _sorted_segment_sum(data, segment_ids, num_segments: int):
    """segment_sum over non-decreasing `segment_ids` (a trie's group ids)."""
    return jax.ops.segment_sum(
        data, segment_ids, num_segments=num_segments, indices_are_sorted=True
    )


def build_trie(
    cols: dict[str, jnp.ndarray],
    lops: _LevelOps,
    *,
    impl: str = "jnp",
    budget: int = 32,
    mult: jnp.ndarray | None = None,
    order: jnp.ndarray | None = None,
    tables: bool = True,
) -> StaticTrie:
    """The explicit build step: columns in, a StaticTrie pytree of device
    arrays out. Traceable — called inside the probe program for raw column
    dicts and weighted stage buffers, or under its own jit (see
    _build_trie_jit) by the cross-call cache. tables=False leaves the
    probed levels' hash tables to the caller (see add_tables)."""
    return StaticTrie(cols, lops, impl, budget, mult=mult, order=order, tables=tables)


@functools.partial(jax.jit, static_argnames=("lops", "impl", "budget", "tables"))
def _build_trie_jit(cols, lops, impl, budget, mult=None, order=None, tables=True):
    return build_trie(
        cols, lops, impl=impl, budget=budget, mult=mult, order=order, tables=tables
    )


def host_sorted_trie(cols: dict, lops: _LevelOps, impl: str, budget: int, mult=None):
    """A build dispatched from the host: a cached or rebuilt relation's
    trie, or a standing query's weighted stage-output trie (`mult`; pads
    carry PAD_KEY keys and mult 0, and the sort routes them to the tail,
    where every later merge expects them). The row order comes from the
    shared sort programs (ops.lex_order), _build_trie_jit adds the group
    structure over it, and the probed levels' tables follow, sorted the same
    way. No program of the build holds a sort of its own: on a TPU each
    would cost 20-60 s of compile time."""
    flat = [v for lv in lops.levels for v in lv]
    trivial = len(lops.levels) == 1 and not lops.probed[0]
    empty = cols[flat[0]].shape[0] == 0
    order = None if trivial or empty else ops.lex_order([cols[v] for v in flat])
    trie = _build_trie_jit(cols, lops, impl, budget, mult=mult, order=order, tables=False)
    return add_tables(trie, lops.probed, budget)


def add_tables(trie: StaticTrie, probed, budget: int) -> StaticTrie:
    """Build (from the host) the hash table of every probed level that has
    none; returns the trie."""
    if not trie.trivial:
        for d, p in enumerate(probed):
            if p and trie.tables[d] is None:
                trie.tables[d] = trie.build_level_table(d, budget, host_sort=True)
    return trie


def _bucket(n: int, block: int = 1024) -> int:
    """Physical capacity for a mutating relation's padded trie: the next
    power of two >= n (min `block`). Appends within a bucket keep every
    array shape fixed — the merge program retraces only at bucket growth."""
    return max(block, 1 << max(0, n - 1).bit_length())


@functools.partial(
    jax.jit,
    static_argnames=("lops", "impl", "budget", "cap", "has_mult"),
)
def _merge_append_jit(
    old_cols,
    old_mult,
    old_sorted,
    old_order,
    n_real,
    delta_cols,
    delta_order,
    *,
    lops,
    impl,
    budget,
    cap,
    has_mult,
):
    """Splice a sorted delta run into a cached padded trie — the delta
    build program. Takes the delta's own sort order (`delta_order`, sorted
    on the host by ops.lex_order; None for a trivial trie), binary-searches
    each delta tuple's slot in the cached sorted run
    (ops.lex_searchsorted), and derives the merged permutation
    arithmetically; the constructor then rebuilds the group structure with
    zero sorting passes.

    Shape discipline: every input keeps its bucket capacity and `n_real`
    (the live+tombstone prefix length) is a DEVICE scalar, so a stream of
    same-size appends within one bucket re-enters one compiled program —
    no retrace per append. Pad rows (keys PAD_KEY, mult 0) sort after all
    real rows, so they stay a contiguous tail that the merge shifts and
    renormalizes with pure elementwise ops; scatters use mode="drop" for
    the pads pushed past the (possibly grown) capacity `cap`."""
    with jax.named_scope("merge"):
        flat = [v for lv in lops.levels for v in lv]
        some = next(iter(delta_cols.values()))
        m = some.shape[0]
        c_old = next(iter(old_cols.values())).shape[0]
        n_new = n_real + m  # dynamic value, static bound cap >= host n_real + m
        idx = jnp.arange(cap, dtype=jnp.int32)

        def extend(a, fill):
            if cap > c_old:
                a = jnp.concatenate([a, jnp.full(cap - c_old, fill, jnp.int32)])
            return a

        new_cols = {}
        for v in old_cols:
            delta = delta_cols[v].astype(jnp.int32)
            new_cols[v] = jax.lax.dynamic_update_slice(
                extend(old_cols[v], PAD_KEY), delta, (n_real,)
            )
        om = old_mult if has_mult else jnp.ones(c_old, jnp.int32)
        om = jnp.where(jnp.arange(c_old, dtype=jnp.int32) < n_real, om, 0)
        new_mult = jax.lax.dynamic_update_slice(
            extend(om, 0), jnp.ones(m, jnp.int32), (n_real,)
        )
        new_mult = jnp.where(idx < n_new, new_mult, 0)
        if len(lops.levels) == 1 and not lops.probed[0]:
            # trivial (cover-only) trie: no order to maintain, just new columns
            return build_trie(
                new_cols, lops, impl=impl, budget=budget, mult=new_mult, tables=False
            )
        # locate each tuple of the sorted delta's splice slot
        ds = {v: delta_cols[v].astype(jnp.int32)[delta_order] for v in flat}
        # rank in the cached sorted run; real keys < PAD_KEY, so ranks never
        # land inside the pad tail and the merged real prefix is exactly n_new
        rank = ops.lex_searchsorted([old_sorted[v] for v in flat], [ds[v] for v in flat])
        pos_delta = rank + jnp.arange(m, dtype=jnp.int32)
        k = jnp.arange(c_old, dtype=jnp.int32)
        pos_old = k + jnp.searchsorted(rank, k, side="right").astype(jnp.int32)
        # delta rows take indices [n_real, n_new); old pads shift up by m
        adj = old_order + jnp.where(old_order >= n_real, m, 0).astype(jnp.int32)
        # both position runs are strictly increasing: sorted scatters, which
        # the TPU compiler takes without sorting their indices
        new_order = jnp.zeros(cap, jnp.int32)
        new_order = new_order.at[pos_old].set(
            adj, mode="drop", indices_are_sorted=True, unique_indices=True
        )
        new_order = new_order.at[pos_delta].set(
            n_real + delta_order, mode="drop", indices_are_sorted=True, unique_indices=True
        )
        # pads are interchangeable: identity-map the tail so `new_order` stays a
        # permutation regardless of how many pads the scatters dropped
        new_order = jnp.where(idx >= n_new, idx, new_order)
        return build_trie(
            new_cols,
            lops,
            impl=impl,
            budget=budget,
            mult=new_mult,
            order=new_order,
            tables=False,  # built from the host by the caller (add_tables)
        )


@jax.jit
def _retire_rows_jit(mult, order, groups, rows):
    """Tombstone catch-up on a cached trie: zero the rows' multiplicity and
    refresh the per-level weight aggregates. The sort order, group
    structure, and hash tables are untouched — dead rows keep their slots
    and simply weigh nothing."""
    mult = mult.at[rows].set(0, indices_are_sorted=True, unique_indices=True)  # np.unique'd
    n = mult.shape[0]
    total = saturating_segment_sum(mult, jnp.zeros(n, jnp.int32), 1)[0]
    sm = mult[order] if order is not None else mult
    weights = [saturating_segment_sum(sm, gd1, n) for gd1 in groups]
    return mult, total, weights


def device_columns(rel) -> dict[str, jnp.ndarray]:
    """Registry-cached int32 device upload of a relation's columns: each
    host column is transferred once per (relation object, column object)
    and the upload dies with the relation. Replacing a column in
    rel.columns re-uploads exactly that column (identity check); mutating a
    numpy array in place is not detectable and not supported — replace the
    array."""
    return {
        v: relcache.memo(
            relcache.REGISTRY,
            rel,
            "dev_cols",
            v,
            rel.columns[v],
            lambda v=v: jnp.asarray(rel.columns[v], jnp.int32),
        )
        for v in rel.schema
    }


class TrieCache:
    """Cross-call StaticTrie cache (see module docstring).

    One entry per (relation object, level layout, impl, budget), held in
    the weakref registry so it dies with the relation; revalidated per
    column by host-array identity, so a replaced column rebuilds. Lazy per
    level: a request probing a level the cached build skipped adds only
    that level's table (build_level_table); a layout with the same level-var
    sequence as a cached one builds over the cached order with no sort.
    Weighted builds are refused — stage-output tries are
    one run's padded lanes and must never be served across runs.

    MUTATING relations (those with a relcache.MutationState, i.e. touched
    by relcache.append/delete) take the versioned DELTA path instead of
    identity revalidation. Their entries carry the mutation version they
    materialized at plus `n_real` (live+tombstone row prefix), and the trie
    itself is padded to a power-of-two bucket — pad rows carry PAD_KEY keys
    and multiplicity 0, sorted to a contiguous tail. Serving one then means:

    * version match — pure cache hit, zero device work;
    * version behind — replay `deltas_since`: an append sorts ONLY the
      delta and splices it into the cached sorted run (_merge_append_jit,
      zero full re-sorts; `delta_merges` counts these), a delete refreshes
      the weight aggregates in place (`tombstone_refreshes`);
    * log pruned / compaction crossed — full padded
      weighted rebuild (counted in `builds`, like any cold build).

    A trie built BEFORE the relation's first mutation is adopted as the
    version-0 merge base when it matches the state's version-0 device
    columns, so warm-then-stream never pays a rebuild.

    Counters (builds/table_builds/hits/order_shares/delta_merges/
    tombstone_refreshes) are the observable contract the tests lock: a
    repeated identical call must be all hits, and an append followed by a
    query must bump delta_merges — never builds.
    """

    def __init__(self, registry: relcache.RelationRegistry | None = None):
        self._reg = registry or relcache.REGISTRY
        self.builds = 0  # full trie builds (sort + structure + tables)
        self.table_builds = 0  # lazy per-level table additions
        self.hits = 0  # fully served from cache: zero device work
        self.order_shares = 0  # builds that reused a cached sort order
        self.delta_merges = 0  # appends absorbed by sorted-run splicing
        self.tombstone_refreshes = 0  # deletes absorbed by weight refresh

    def get(
        self,
        rel,
        dev_cols: dict[str, jnp.ndarray],
        lops: _LevelOps,
        *,
        impl: str = "jnp",
        budget: int = 32,
        mult=None,
    ) -> StaticTrie:
        """The trie of `rel` under `lops`, in the span `fj.trie.get` whose
        stat `outcome` says what serving it took: a full build, a merge of
        the delta log, a lazy table, or a hit."""
        assert mult is None, "weighted (stage-output) tries are never cached"
        with obs.span("fj.trie.get") as sp:
            before = self._tally()
            trie = self._get(rel, dev_cols, lops, impl, budget)
            moved = (o for o, b, a in zip(self._OUTCOMES, before, self._tally()) if a != b)
            sp.stats(outcome=next(moved, "hit"))
        return trie

    _OUTCOMES = ("build", "merge", "table")

    def _tally(self) -> tuple[int, int, int]:
        """Work done so far, in `_OUTCOMES` order."""
        return self.builds, self.delta_merges + self.tombstone_refreshes, self.table_builds

    def _get(self, rel, dev_cols, lops, impl, budget) -> StaticTrie:
        ns = self._reg.namespace(rel, "tries")
        flat = tuple(v for lv in lops.levels for v in lv)
        used = {v: dev_cols[v] for v in flat}
        trivial = len(lops.levels) == 1 and not lops.probed[0]
        # trivial-ness is part of the identity: a cover-only (table-less,
        # order-less) trie must never be served to a schedule that probes
        key = (lops.levels, impl, budget, trivial)
        st = relcache.mutation_state(rel)
        if st is not None:
            return self._get_mutating(rel, st, dev_cols, lops, flat, key, impl, budget)
        entry = ns.get(key)
        if (
            entry is not None
            and entry.get("version") is None
            and all(entry["cols"][v] is used[v] for v in flat)
        ):
            view = self._serve(entry["trie"], lops, budget, count_hit=True)
            self._govern(rel, ns, key)
            return view
        # miss: build over the host-sorted row order, or over the order of
        # a cached trie whose level vars are the same sequence over the
        # same (identical) columns, which needs no sort at all
        donor_order = None
        if not trivial:
            for (levels2, _i2, _b2, _t2), e2 in ns.items():
                donor = e2["trie"]
                if donor.order is None or e2.get("version") is not None:
                    continue  # padded mutating orders never seed plain builds
                flat2 = tuple(v for lv in levels2 for v in lv)
                if flat2 == flat and all(e2["cols"][v] is used[v] for v in flat):
                    donor_order = donor.order
                    break
        if donor_order is None:
            trie = host_sorted_trie(used, lops, impl, budget)
        else:
            trie = _build_trie_jit(used, lops, impl, budget, order=donor_order, tables=False)
            trie = add_tables(trie, lops.probed, budget)
            self.order_shares += 1
        ns[key] = {"trie": trie, "cols": used}
        self.builds += 1
        self._govern(rel, ns, key)
        return trie.table_view(lops.probed)

    def _govern(self, rel, ns, key) -> None:
        """Account the cached entry's device bytes with the memory
        governor (an LRU touch on every serve, a resize when lazy tables
        or delta merges changed the footprint). If the governor sheds —
        this trie alone cannot fit the budget even after evicting every
        cold entry — the entry is dropped and the trie serves this one
        call uncached, keeping the governed-bytes invariant intact."""
        entry = ns.get(key)
        if entry is None:
            return
        token = ("trie", id(rel), key)
        try:
            membudget.GOVERNOR.account(
                token,
                membudget.trie_nbytes(entry["trie"]),
                evict=lambda _ns=ns, _k=key: _ns.pop(_k, None),
                owner=rel,
            )
        except membudget.MemoryBudgetError:
            ns.pop(key, None)
            membudget.GOVERNOR.release(token)

    def _serve(self, trie: StaticTrie, lops, budget, *, count_hit: bool):
        """Fill any probe tables the request needs that the cached build
        skipped (the lazy-COLT path), then hand out a probed view."""
        missing = [
            d
            for d, p in enumerate(lops.probed)
            if p and not trie.trivial and trie.tables[d] is None
        ]
        for d in missing:
            trie.tables[d] = trie.build_level_table(d, budget, host_sort=True)
            self.table_builds += 1
        if count_hit and not missing:
            self.hits += 1
        return trie.table_view(lops.probed)

    def _get_mutating(self, rel, st, dev_cols, lops, flat, key, impl, budget):
        """Serve a mutating relation: version-matched hit, delta catch-up
        (merge appends, retire deletes), or full padded rebuild."""
        ns = self._reg.namespace(rel, "tries")
        entry = ns.get(key)
        if entry is not None and entry.get("version") is None:
            # built before the first mutation: adopt as the version-0 merge
            # base iff it is over the state's version-0 device columns (and
            # no compaction/pruning has moved the base past version 0)
            trie = entry["trie"]
            if (
                st.base_version == 0
                and not trie.empty
                and all(entry["cols"].get(v) is st.dev0.get(v) for v in flat)
            ):
                entry["version"] = 0
                entry["n_real"] = trie.n
            else:
                entry = None
        deltas = None
        if entry is not None:
            deltas = st.deltas_since(entry["version"])
            if deltas is None or entry["trie"].empty:
                entry = None  # pruned log or sentinel empty trie: rebuild
        if entry is not None:
            trie = entry["trie"]
            if not deltas:
                view = self._serve(trie, lops, budget, count_hit=True)
                self._govern(rel, ns, key)
                return view
            for _ver, kind, payload in deltas:
                if kind == "append":
                    trie = self._merge_append(
                        trie, entry["n_real"], payload, lops, impl, budget
                    )
                    entry["n_real"] += len(next(iter(payload.values())))
                    self.delta_merges += 1
                else:
                    self._retire(trie, payload)
                    self.tombstone_refreshes += 1
            if entry is not None:
                entry["trie"] = trie
                entry["cols"] = dict(trie.cols)
                entry["version"] = st.version
                view = self._serve(trie, lops, budget, count_hit=False)
                self._govern(rel, ns, key)
                return view
        # full rebuild, padded to the bucket and weighted by the liveness
        # mask, so later appends merge and later deletes retire in place
        cap = _bucket(st.total)
        pad = cap - st.total
        used = {}
        for v in flat:
            dc = dev_cols[v]
            used[v] = (
                jnp.concatenate([dc, jnp.full(pad, PAD_KEY, jnp.int32)]) if pad else dc
            )
        if st.mult is not None:
            hm = st.mult if pad == 0 else np.concatenate([st.mult, np.zeros(pad, np.int32)])
            mult = jax.device_put(np.ascontiguousarray(hm))
        else:
            mult = (jnp.arange(cap, dtype=jnp.int32) < st.total).astype(jnp.int32)
        trie = host_sorted_trie(used, lops, impl, budget, mult=mult)
        ns[key] = {
            "trie": trie,
            "cols": dict(trie.cols),
            "version": st.version,
            "n_real": st.total,
        }
        self.builds += 1
        view = self._serve(trie, lops, budget, count_hit=False)
        self._govern(rel, ns, key)
        return view

    def _merge_append(self, trie, n_real, payload, lops, impl, budget):
        """Host wrapper for one append log entry: bucket growth, explicit
        device_put of the delta, its sort order (ops.lex_order), and the
        probed-union lops (a merge rebuilds every table the cached trie had
        accumulated, so other schedules stay warm)."""
        flat = tuple(v for lv in lops.levels for v in lv)
        m = len(next(iter(payload.values())))
        cap = _bucket(n_real + m)
        delta_dev = {
            v: jax.device_put(np.ascontiguousarray(payload[v].astype(np.int32)))
            for v in flat
        }
        delta_order = None
        if trie.trivial:
            mlops = lops
        else:
            delta_order = ops.lex_order([delta_dev[v] for v in flat])
            mlops = replace(
                lops,
                probed=tuple(
                    (t is not None) or p for t, p in zip(trie.tables, lops.probed)
                ),
            )
        merged = _merge_append_jit(
            {v: trie.cols[v] for v in flat},
            trie.mult_col,
            trie.sorted_cols,
            trie.order,
            jax.device_put(np.int32(n_real)),
            delta_dev,
            delta_order,
            lops=mlops,
            impl=impl,
            budget=budget,
            cap=cap,
            has_mult=trie.mult_col is not None,
        )
        return add_tables(merged, mlops.probed, budget)

    def _retire(self, trie, rows):
        """Apply one delete log entry to the cached trie in place: rows are
        host positions, which by the padding invariant are trie row indices
        verbatim. Order, groups, and tables are untouched."""
        mult = trie.mult_col
        if mult is None:
            mult = jnp.ones(trie.n, jnp.int32)
        groups = [] if trie.trivial else trie.g[1:]
        mult, total, weights = _retire_rows_jit(
            mult, trie.order, groups, jax.device_put(rows)
        )
        trie.mult_col = mult
        trie.total_mult = total
        if not trie.trivial:
            trie.row_weight = weights


TRIE_CACHE = TrieCache()


def make_executor(
    plan: FreeJoinPlan,
    capacities,
    *,
    compact_to=None,
    compact_probe=None,
    impl: str = "jnp",
    budget: int = 32,
    agg: str | None = "count",
    schedule: StaticSchedule | None = None,
    filters: tuple = (),
    filter_kill: bool = True,
):
    """Build a jit-able probe program for `plan` (see module docstring).

    capacities: one static expansion capacity per executed node; compact_to:
    optional per-node compaction target (None = keep the buffer);
    compact_probe: per node, how many probes run before compacting (default
    all — compact after the node; smaller values compact mid-node so the
    remaining probes run at the squeezed width); schedule: the query's
    StaticSchedule if the driver already computed it (None = walk the plan
    here). Returns fn(rel_data, rel_mults) ->
      agg="count":  (count words, need_expand, need_compact)
      agg=None:     (bound, valid, mult, need_expand, need_compact)
    The count words are wide_count's (read them with count_value); in an
    agg=None output a refused lane carries mult MULT_SAT.
    rel_data maps alias -> either a prebuilt StaticTrie (the warm path:
    zero build work in this call) or {var: (N,) int32} raw columns (built
    in-graph — the cold path, and the only path for weighted stage
    buffers). rel_mults (optional) maps an alias to a per-row multiplicity
    vector; such a relation is a *weighted* (stage-output) buffer whose
    mult-0 rows are padding — see StaticTrie. rel_data may contain extra
    aliases (the chain driver passes one growing dict); only the plan's are
    read. need_expand/need_compact are (num_executed_nodes,) int32 vectors
    of required totals: need_expand[i] is the lane count node i's expansion
    produced, need_compact[i] the live count at its compact point (0 when
    the node doesn't expand/compact). Node i overflowed iff
    need_expand[i] > capacities[i] (resp. need_compact[i] > compact_to[i]);
    the need is the exact capacity the adaptive runner should jump to.

    filters: ((var, const_index), ...) — equality selections whose
    *constants live outside the compiled program*: the run fn gains a
    third argument `filter_consts`, a traced int32 vector, compared
    against `bound[var]` the moment `var` is bound. Because the constant
    is a runtime value, every query of a plan template (same structure,
    different constants) shares ONE compiled executor. Two dispositions
    for the comparison's outcome:

    * filter_kill=True (single-query serving): the comparison ANDs into
      `valid` — filter-dead lanes stop probing immediately and compaction
      squeezes them out, so a selective constant makes the whole run
      cheaper.
    * filter_kill=False (batched serving): the comparison ANDs into a
      SEPARATE per-lane mask (`fvalid`) that rides along the frontier and
      folds in only at the terminal count/output. `valid`, every
      expansion count, every compaction, every probe — the entire
      frontier *layout* — stays constant-independent, so under jax.vmap
      over a (B, F) constants matrix the whole probe pipeline is computed
      ONCE and shared across lanes; only the mask ops and the final
      reduction batch. This is what makes one batched dispatch of B
      queries cost ~one unfiltered query instead of B filtered ones.
    """
    plan.validate()
    filters = tuple(filters)
    filter_idx = {v: int(i) for v, i in filters}
    unknown = set(filter_idx) - set(plan.query.variables)
    assert not unknown, f"filter vars not bound by this plan: {sorted(unknown)}"
    if schedule is None:
        schedule = _static_schedule(plan)
    level_ops = schedule.level_ops
    schedule = schedule.entries
    nsched = len(schedule)
    capacities = tuple(int(c) for c in capacities[:nsched])
    assert len(capacities) == nsched, "one capacity per executed node"
    compact_to = tuple(compact_to[:nsched]) if compact_to is not None else (None,) * nsched
    assert len(compact_to) == nsched, "one compaction target per executed node"
    compact_probe = (
        tuple(compact_probe[:nsched])
        if compact_probe
        else tuple(len(probes) for _, _, probes in schedule)
    )
    assert len(compact_probe) == nsched, "one compact point per executed node"

    def as_trie(src, lops: _LevelOps, mult):
        if isinstance(src, StaticTrie):
            assert src.levels == lops.levels, "prebuilt trie level mismatch"
            for d, p in enumerate(lops.probed):
                assert not p or src.trivial or src.tables[d] is not None, (
                    f"prebuilt trie missing probed level-{d} table"
                )
            return src
        return build_trie(src, lops, impl=impl, budget=budget, mult=mult)

    def run(
        rel_data: dict[str, object],
        rel_mults: dict[str, jnp.ndarray] | None = None,
        filter_consts: jnp.ndarray | None = None,
    ):
        assert not filter_idx or filter_consts is not None, (
            "this executor was built with filters; pass filter_consts"
        )
        mults = rel_mults or {}
        tries = {
            a: as_trie(rel_data[a], level_ops[a], mults.get(a)) for a in level_ops
        }
        depth = {a: 0 for a in level_ops}
        # frontier
        cap = 1
        valid = jnp.ones(1, dtype=bool)
        # int32 per lane, every product checked (mul_checked); the count
        # itself is summed wide at the end (wide_count)
        mult = jnp.ones(1, jnp.int32)
        bound: dict[str, jnp.ndarray] = {}
        gid: dict[str, jnp.ndarray] = {}
        # mask-mode filter state (filter_kill=False): per-lane liveness that
        # never feeds the frontier layout — created at the first filter
        # comparison, gathered alongside the frontier, folded in at the end
        fvalid: list = [None]
        need_expand = [jnp.zeros((), jnp.int32) for _ in range(nsched)]
        need_compact = [jnp.zeros((), jnp.int32) for _ in range(nsched)]

        def squeeze(bound, gid, mult, valid, cap, c_compact, i):
            """Pack the valid lanes into a fresh c_compact-wide frontier."""
            with jax.named_scope("compact"):
                src, live = ops.compact_indices(valid, c_compact, impl=impl)
                need_compact[i] = live
                srcc = jnp.clip(src, 0, cap - 1)
                bound = {v: a[srcc] for v, a in bound.items()}
                gid = {a: arr[srcc] for a, arr in gid.items()}
                mult = mult[srcc]
                if fvalid[0] is not None:
                    fvalid[0] = fvalid[0][srcc]
                valid = jnp.arange(c_compact, dtype=jnp.int32) < live
            return bound, gid, mult, valid, c_compact

        for i, ((k, cover, probes), c_next, c_compact, cp_idx) in enumerate(
            zip(schedule, capacities, compact_to, compact_probe)
        ):
            # named scopes reach the device trace as each op's name: node i,
            # then its stage (expand, probe, compact, count)
            with jax.named_scope(f"node{i}"):
                t = tries[cover.alias]
                d = depth[cover.alias]
                g = gid.get(cover.alias, jnp.zeros(cap, jnp.int32))
                last = d == t.L - 1
                # a filtered var can never take the factorized-count shortcut:
                # its comparison against the constant needs the bound values
                needed = _needed_later_static(plan, k, probes, agg) | set(filter_idx)
                if agg == "count" and not (set(cover.vars) & needed) and last and not (
                    set(cover.vars) & set(bound)
                ):
                    # factorized count (static decision)
                    with jax.named_scope("count"):
                        rows = t.rows_under(d, g)
                        mult = mul_checked(mult, jnp.where(valid, rows, 1))
                    gid.pop(cover.alias, None)
                    depth[cover.alias] = t.L
                else:
                    with jax.named_scope("expand"):
                        base, counts = t.iter_counts(d, g, last)
                        counts = jnp.where(valid, counts, 0)
                        fr, member, vnew, total = ops.expand_counted(
                            base, counts, c_next, impl=impl
                        )
                        need_expand[i] = total
                        frc = jnp.clip(fr, 0, cap - 1)
                        memc = jnp.clip(member, 0, max(t.n - 1, 0))
                        bound = {v: a[frc] for v, a in bound.items()}
                        gid = {a: arr[frc] for a, arr in gid.items()}
                        mult = mult[frc]
                        if fvalid[0] is not None:
                            fvalid[0] = fvalid[0][frc]
                        valid = vnew
                        cap = c_next
                        cols, new_g = t.bind_iter(d, memc, last)
                        for v, cvals in zip(cover.vars, cols):
                            if v in bound:  # semijoin on re-bound vars
                                valid = valid & (bound[v] == cvals)
                            else:
                                bound[v] = cvals
                                if v in filter_idx:  # constant selection, applied
                                    # the moment the var is bound
                                    hit = cvals == filter_consts[filter_idx[v]]
                                    if filter_kill:  # dead lanes never reach a probe
                                        valid = valid & hit
                                    elif fvalid[0] is None:  # layout-neutral mask
                                        fvalid[0] = hit
                                    else:
                                        fvalid[0] = fvalid[0] & hit
                        depth[cover.alias] = d + 1
                        if new_g is None or depth[cover.alias] == t.L:
                            # last-level iteration enumerates physical rows, so bag
                            # multiplicity is already accounted for — except on a
                            # weighted (stage-output) trie, whose per-row mult folds
                            # in here and whose mult-0 pad rows die on the spot.
                            rm = t.iter_mult(memc)
                            if rm is not None:
                                mult = mul_checked(mult, jnp.where(valid, rm, 1))
                                valid = valid & (rm > 0)
                            gid.pop(cover.alias, None)
                        else:
                            gid[cover.alias] = new_g
                compacted = False
                for j, sa in enumerate(probes):
                    with jax.named_scope("probe"):
                        tp = tries[sa.alias]
                        dp = depth[sa.alias]
                        gp = gid.get(sa.alias, jnp.zeros(cap, jnp.int32))
                        keys = [bound[v] for v in sa.vars]
                        child = tp.probe(dp, jnp.where(valid, gp, -1), keys)
                        valid = valid & (child >= 0)
                        childc = jnp.clip(child, 0, max(tp.n - 1, 0))
                        depth[sa.alias] = dp + 1
                        if depth[sa.alias] == tp.L:
                            rows = tp.rows_under(tp.L, childc)
                            mult = mul_checked(mult, jnp.where(valid, rows, 1))
                            gid.pop(sa.alias, None)
                        else:
                            gid[sa.alias] = childc
                    if (
                        c_compact is not None
                        and not compacted
                        and j + 1 >= cp_idx
                        and c_compact < cap
                    ):
                        # squeeze dead lanes out mid-node: the remaining probes
                        # (and all later nodes) run at c_compact
                        bound, gid, mult, valid, cap = squeeze(
                            bound, gid, mult, valid, cap, c_compact, i
                        )
                        compacted = True
                if c_compact is not None and not compacted and c_compact < cap:
                    # probe-less node (or unreached compact point): after-node
                    bound, gid, mult, valid, cap = squeeze(
                        bound, gid, mult, valid, cap, c_compact, i
                    )
        ne = jnp.stack(need_expand) if nsched else jnp.zeros(0, jnp.int32)
        nc = jnp.stack(need_compact) if nsched else jnp.zeros(0, jnp.int32)
        with jax.named_scope("count"):
            if fvalid[0] is not None:  # mask-mode filters fold in only here
                valid = valid & fvalid[0]
            if agg == "count":
                return wide_count(mult, valid), ne, nc
            # lanes that went through a weighted trie's probe path can survive
            # with mult 0 (pad groups weigh nothing); they are not output rows
            valid = valid & (mult > 0)
        return bound, valid, mult, ne, nc

    return run


def overflows(cap_plan, need_expand, need_compact):
    """Per-node overflow bits from the executor's reported needs and the
    capacity plan the run used: (ovf_expand, ovf_compact) bool arrays."""
    ne = np.asarray(need_expand)
    nc = np.asarray(need_compact)
    caps = np.asarray(cap_plan.capacities, np.int64)
    cts = np.array(
        [np.iinfo(np.int64).max if c is None else c for c in cap_plan.compact_to], np.int64
    )
    return ne > caps, nc > cts


def make_chain_executor(
    stages,
    cap_plans,
    *,
    impl: str = "jnp",
    budget: int = 32,
    agg: str | None = "count",
    filter_vars: tuple[str, ...] = (),
    filter_kill: bool = True,
):
    """One on-device program for a whole bushy plan (Sec 2.2 stages).

    stages: ((name, FreeJoinPlan), ...) with the root stage last — each plan
    may reference earlier stages' names as relation aliases; cap_plans: one
    CapacityPlan per stage (schedule riding along). Every non-root stage
    runs its make_executor with agg=None, its output columns stay on device
    as a padded buffer (invalid lanes stamped PAD_KEY, multiplicity 0), and
    the next stage builds a weighted StaticTrie straight from that buffer —
    no host round-trip, no eager engine. Returns
        run(rel_data) -> (root outputs..., need_expand_t, need_compact_t)
    where rel_data holds the *base* relations only — prebuilt StaticTries
    or raw column dicts per alias, exactly as make_executor accepts — and
    the need vectors are per-stage tuples (one (num_nodes,) int32 vector
    each, stage order). Stage-output tries are always built in-graph: they
    are weighted buffers of this one run and never cacheable.

    filter_vars names equality-selected vars (plan-template constants, see
    make_executor): run gains a `filter_consts` int32 vector in
    filter_vars order, and each var's comparison runs in the FIRST stage
    that binds it — filtered rows carry mult 0 into downstream weighted
    tries, so later stages never re-check. filter_kill picks the
    comparison's disposition (see make_executor); in mask mode a non-root
    stage's terminal fold still stamps filter-dead rows mult-0, so later
    stages of a batched chain run per-lane — single-stage plans are the
    fully-shared fast path."""
    assert len(stages) == len(cap_plans) >= 1, "one capacity plan per stage"
    filter_vars = tuple(filter_vars)
    unassigned = dict((v, i) for i, v in enumerate(filter_vars))
    fns = []
    for i, ((_name, plan), cp) in enumerate(zip(stages, cap_plans)):
        stage_filters = tuple(
            (v, unassigned.pop(v)) for v in tuple(plan.query.variables) if v in unassigned
        )
        fns.append(
            make_executor(
                plan,
                cp.capacities,
                compact_to=cp.compact_to,
                compact_probe=getattr(cp, "compact_probe", ()),
                impl=impl,
                budget=budget,
                agg=agg if i == len(stages) - 1 else None,
                schedule=cp.schedule,
                filters=stage_filters,
                filter_kill=filter_kill,
            )
        )
    assert not unassigned, f"filter vars not bound by any stage: {sorted(unassigned)}"

    def run(rel_data: dict[str, object], filter_consts: jnp.ndarray | None = None):
        cols = dict(rel_data)
        stage_mults: dict[str, jnp.ndarray] = {}
        nes, ncs = [], []
        for (name, plan), fn in zip(stages[:-1], fns[:-1]):
            bound, valid, mult, ne, nc = fn(cols, stage_mults, filter_consts)
            head = plan.query.head
            cols[name] = {v: jnp.where(valid, bound[v], PAD_KEY) for v in head}
            stage_mults[name] = jnp.where(valid, mult, 0).astype(jnp.int32)
            nes.append(ne)
            ncs.append(nc)
        out = fns[-1](cols, stage_mults, filter_consts)
        nes.append(out[-2])
        ncs.append(out[-1])
        return out[:-2] + (tuple(nes), tuple(ncs))

    return run


def make_count_fn(
    plan: FreeJoinPlan,
    capacities: list[int],
    impl: str = "jnp",
    budget: int = 32,
    *,
    schedule: StaticSchedule | None = None,
):
    """Original count-only surface: fn(rel_cols) -> (count words,
    overflowed); count_value reads the words.
    One scalar overflow flag; no compaction. Kept for benchmarks and dry
    runs — the SPMD driver (core/distributed.py) uses make_executor's need
    vectors directly so its retry loop can grow the offending node."""
    if schedule is None:
        schedule = _static_schedule(plan)
    inner = make_executor(
        plan, capacities, impl=impl, budget=budget, agg="count", schedule=schedule
    )
    caps = jnp.asarray(
        tuple(int(c) for c in capacities[: len(schedule)]) or (0,), jnp.int32
    )

    def run(rel_cols):
        count, ne, nc = inner(rel_cols)
        return count, (ne > caps[: ne.shape[0]]).any()

    return run


def _needed_later_static(plan: FreeJoinPlan, k: int, probes, agg: str | None = "count") -> set[str]:
    need: set[str] = set()
    for sa in probes:
        need |= set(sa.vars)
    for node in plan.nodes[k + 1 :]:
        for sa in node:
            need |= set(sa.vars)
    if agg != "count":
        need |= set(plan.query.head)
    return need


def count_query(
    plan: FreeJoinPlan,
    relations,
    capacities: list[int],
    impl: str = "jnp",
    jit: bool = True,
    budget: int = 32,
):
    """Convenience: run the compiled COUNT on host numpy relations."""
    rel_cols = relations_to_cols(plan, relations)
    fn = make_count_fn(plan, capacities, impl, budget)
    if jit:
        fn = jax.jit(fn)
    count, overflow = fn(rel_cols)
    return count_value(count), bool(overflow)


def relations_to_cols(plan: FreeJoinPlan, relations) -> dict[str, dict[str, jnp.ndarray]]:
    """Device int32 columns for every alias the plan touches."""
    return stage_relations_to_cols((("__root", plan),), relations)


def _base_aliases(stages) -> set[str]:
    """Every relation alias a stage chain reads from the caller — stage
    names are produced on device by the chain executor, never read."""
    names = {name for name, _ in stages}
    return {sa.alias for _, plan in stages for node in plan.nodes for sa in node} - names


def stage_relations_to_cols(stages, relations) -> dict[str, dict[str, jnp.ndarray]]:
    """Device int32 columns for every *base* alias a stage chain touches."""
    return {
        a: {v: jnp.asarray(relations[a].columns[v], jnp.int32) for v in relations[a].schema}
        for a in _base_aliases(stages)
    }


class AdaptiveExecutor:
    """Overflow-retrying driver around the chained executor (see module
    docstring).

    Accepts a single FreeJoinPlan + CapacityPlan (the classic one-stage
    surface) or a full stage chain — ((name, plan), ...) root last — with a
    ChainCapacityPlan; either way the whole program runs as ONE compiled
    call. If any stage's node reports a need above its capacity, jumps
    exactly that node's capacity (or compaction target) to the reported
    need and re-runs — one retry per offending node, not a doubling ladder.
    Compiled executors are cached per capacity-vector chain and the grown
    plan replaces the initial one, so a stream of similar queries pays the
    retry + recompile once and then runs overflow-free.

    run_relations is the warm serving surface: device uploads come from the
    per-relation registry and base tries from the cross-call TRIE_CACHE, so
    repeated calls over the same relations — and every overflow/tighten
    re-run — pay probe cost only. Calling the executor directly with raw
    column dicts keeps the cold (build-in-graph) behavior.

    Serving extensions (the multi-tenant path, see serve/join_engine.py):

    * filter_vars — equality selections whose constants are runtime inputs
      (plan templates): __call__ takes a `filter_consts` int32 vector in
      filter_vars order, and one compiled executor serves every constant.
    * batch=B — the whole chain is vmapped over filter_consts, so ONE
      device dispatch runs B queries of the template against the SAME
      shared tries: filter_consts becomes (B, F), counts come back (B,),
      and need vectors come back per lane. Overflow growth uses the
      per-node max across lanes (the chain's static shapes are shared).
    * max_capacity — per-node growth quota: a need that would grow any
      node past it raises capacity.CapacityQuotaError naming the offending
      batch lane instead of recompiling the shared executor, so admission
      control can reject exactly that request.
    """

    def __init__(
        self,
        plan,
        cap_plan,
        *,
        impl: str = "jnp",
        budget: int = 32,
        agg: str | None = "count",
        jit: bool = True,
        max_retries: int = 12,
        tighten: bool = False,
        filter_vars: tuple[str, ...] = (),
        batch: int | None = None,
        max_capacity: int | None = None,
    ):
        from repro.core.capacity import ChainCapacityPlan  # deferred: no cycle

        stages = (
            (("__root", plan),)
            if isinstance(plan, FreeJoinPlan)
            else tuple((name, p) for name, p in plan)
        )
        chain = (
            cap_plan
            if isinstance(cap_plan, ChainCapacityPlan)
            else ChainCapacityPlan(names=tuple(n for n, _ in stages), stages=(cap_plan,))
        )
        assert len(chain.stages) == len(stages), "one capacity plan per stage"
        # reuse the schedules the planner already computed, if they rode along
        chain = chain.with_schedules(
            tuple(
                cp.schedule if cp.schedule is not None else _static_schedule(p)
                for cp, (_n, p) in zip(chain.stages, stages)
            )
        )
        for _name, p in stages:
            p.validate()
        self.stages = stages
        self._single = len(stages) == 1
        self.plan = stages[-1][1]  # the root stage plan
        self.cap_plan = chain.stages[0] if self._single else chain
        self.schedules = tuple(cp.schedule for cp in chain.stages)
        self.schedule = self.schedules[-1]
        self.impl = impl
        self.budget = budget
        self.agg = agg
        self.jit = jit
        self.max_retries = max_retries
        self.tighten = tighten
        self.filter_vars = tuple(filter_vars)
        self.batch = batch
        self.max_capacity = max_capacity
        assert batch is None or self.filter_vars, (
            "batched execution varies only the constant vector per lane; "
            "a template with no filters should run once, unbatched"
        )
        self.retries = 0  # total overflow re-runs across calls
        self.reshapes = 0  # tightening re-runs across calls
        self.calls = 0  # top-level call chains issued (retries excluded)
        self._cache: dict[tuple, object] = {}
        # memory-governor token, set by api._govern_runner when this runner
        # is cached: growth re-accounts against the budget and sheds
        # (MemoryBudgetError -> the serving ladder) instead of allocating
        self._govern_token = None
        self._last_needs = None  # per-stage measured expansion needs (lane counts)
        self._feedback_specs = None  # lazily-derived per-node prefix specs
        # base alias -> its level layout (for cross-call trie reuse); an
        # alias read under two different layouts falls back to raw columns
        base = _base_aliases(stages)
        self._alias_lops: dict[str, _LevelOps | None] = {}
        for sched in self.schedules:
            for a, lo in sched.level_ops.items():
                if a not in base:
                    continue
                if a in self._alias_lops and self._alias_lops[a] != lo:
                    self._alias_lops[a] = None
                else:
                    self._alias_lops.setdefault(a, lo)

    @property
    def compiles(self) -> int:
        return len(self._cache)

    def _as_chain(self, cp):
        from repro.core.capacity import ChainCapacityPlan  # deferred: no cycle

        if isinstance(cp, ChainCapacityPlan):
            return cp
        return ChainCapacityPlan(names=tuple(n for n, _ in self.stages), stages=(cp,))

    def frontier_nbytes(self, cap_plan=None) -> int:
        """Accounting model of this runner's frontier footprint: per stage,
        cells x 4 bytes x (bound vars + valid + mult), plus per-lane mask
        columns for batched (mask-mode) runners. The governor's currency
        for runner-cache entries and adaptive growth."""
        chain = self._as_chain(self.cap_plan if cap_plan is None else cap_plan)
        total = 0
        for (_name, p), cp in zip(self.stages, chain.stages):
            width = len(tuple(p.query.variables)) + 2
            total += cp.cells() * 4 * width
            if self.batch:
                total += cp.cells() * 4 * self.batch
        return total

    def _fn(self, chain):
        key = chain.key()
        if key not in self._cache:
            faults.fire("compile")
            fn = make_chain_executor(
                self.stages,
                chain.stages,
                impl=self.impl,
                budget=self.budget,
                agg=self.agg,
                filter_vars=self.filter_vars,
                # batched runs use mask-mode filters so the frontier layout
                # is shared across lanes; single-query runs keep kill mode
                # (lane death feeds compaction, a selective constant is
                # genuinely cheaper)
                filter_kill=self.batch is None,
            )
            if self.batch is not None:
                # one dispatch for the whole template batch: tries are
                # broadcast (in_axes=None), only the constant vector is
                # mapped — pre-filter work stays unbatched inside vmap
                fn = jax.vmap(fn, in_axes=(None, 0))
            self._cache[key] = jax.jit(fn) if self.jit else fn
        return self._cache[key]

    def _reduced(self, need):
        """Per-node need vector of a (possibly per-lane) reported need:
        batched runs report (B, n); the chain's static shapes are shared,
        so growth follows the max over lanes."""
        need = np.asarray(need)
        return need.max(axis=0) if need.ndim == 2 else need

    def _check_quota(self, chain, s: int, i: int, need: int, per_lane) -> None:
        from repro.core.capacity import CapacityQuotaError, _round_block

        if self.max_capacity is None:
            return
        cp = chain.stages[s]
        target = max(2 * cp.capacities[i], _round_block(int(need), cp.block))
        if target <= self.max_capacity:
            return
        lane = None
        if per_lane.ndim == 2:
            lane = int(np.argmax(per_lane[:, i]))
        raise CapacityQuotaError(s, i, int(need), self.max_capacity, lane=lane)

    def __call__(self, rel_data: dict[str, object], filter_consts=None):
        """agg="count" -> count words (count_value reads them); agg=None ->
        (bound, valid, mult).
        rel_data values are prebuilt StaticTries and/or raw column dicts
        (see make_executor). filter_consts: (F,) int32 in filter_vars
        order — or (batch, F) for a batched runner, which returns (B, W)
        count words (agg="count") or per-lane (bound, valid, mult). The call
        is the span `fj.executor.call`, with a sequence id as its stat."""
        with obs.span("fj.executor.call", seq=obs.seq()):
            return self._call(rel_data, filter_consts)

    def _call(self, rel_data, filter_consts):
        from repro.core.capacity import _round_block  # deferred: no cycle

        if self.filter_vars:
            assert filter_consts is not None, "this runner's template has filters"
            # explicit h2d (device_put), not jnp.asarray: the warm serving
            # step must hold under jax.transfer_guard("disallow") — every
            # remaining transfer in this driver is deliberate and visible
            filter_consts = (
                filter_consts.astype(jnp.int32)
                if isinstance(filter_consts, jax.Array)
                else jax.device_put(np.asarray(filter_consts, np.int32))
            )
            want = (self.batch, len(self.filter_vars)) if self.batch else (
                len(self.filter_vars),
            )
            assert filter_consts.shape == want, (filter_consts.shape, want)
        chain = self._as_chain(self.cap_plan)
        self.calls += 1
        tightened = False
        faults.fire("overflow", batch=self.batch, max_capacity=self.max_capacity)
        for _ in range(self.max_retries + 1):
            with obs.span("fj.executor.dispatch"):
                fn = self._fn(chain)
                faults.fire("dispatch")
                out = fn(rel_data, filter_consts) if self.filter_vars else fn(rel_data)
                obs.count("executor.dispatches")
            # ONE explicit d2h for the control plane: the per-stage need
            # vectors drive host-side overflow/tighten decisions. Results
            # stay on device until the caller reads them.
            needs_e, needs_c = obs.read((out[-2], out[-1]), "fj.executor.sync")
            grown = chain
            for s, (cp, ne_l, nc_l) in enumerate(zip(chain.stages, needs_e, needs_c)):
                ne, nc = self._reduced(ne_l), self._reduced(nc_l)
                oe, oc = overflows(cp, ne, nc)
                for i in np.flatnonzero(oc):
                    grown = grown.grow_to(s, int(i), int(nc[i]), compaction=True)
                for i in np.flatnonzero(oe):
                    self._check_quota(chain, s, int(i), int(ne[i]), np.asarray(ne_l))
                    grown = grown.grow_to(s, int(i), int(ne[i]))
            if grown is not chain:
                with obs.span("fj.executor.grow"):
                    if self._govern_token is not None:
                        # growth must fit the device-memory budget: a shed
                        # here raises MemoryBudgetError into the degradation
                        # ladder instead of growing past what the device holds
                        membudget.GOVERNOR.account(
                            self._govern_token, self.frontier_nbytes(grown)
                        )
                chain = grown
                self.retries += 1
                continue
            if self.tighten and not tightened:
                # success with measured needs in hand: shrink any buffer
                # that ran >2x oversized and re-run once at the tight
                # shapes, so steady state pays for measured frontiers, not
                # for planning estimates (the planner only has to be right
                # on average; the measurement is exact)
                shrunk = chain
                for s, (ne, nc) in enumerate(zip(needs_e, needs_c)):
                    ne, nc = self._reduced(ne), self._reduced(nc)
                    for i in range(len(ne)):
                        cp = shrunk.stages[s]
                        if cp.capacities[i] > 2 * _round_block(int(ne[i]), cp.block):
                            shrunk = shrunk.shrink_to(s, i, int(ne[i]))
                        ct = shrunk.stages[s].compact_to[i]
                        if ct is not None and ct > 2 * _round_block(int(nc[i]), cp.block):
                            shrunk = shrunk.shrink_to(s, i, int(nc[i]), compaction=True)
                if shrunk is not chain:
                    chain = shrunk
                    tightened = True
                    self.reshapes += 1
                    continue
            # steady state: keep the grown/tightened plan
            self.cap_plan = chain.stages[0] if self._single else chain
            if self._govern_token is not None:
                membudget.GOVERNOR.account(
                    self._govern_token, self.frontier_nbytes(chain)
                )
            # stash the measured per-node expansion needs: exact frontier
            # lane counts, the optimizer's measured-cardinality feedback
            self._last_needs = tuple(self._reduced(ne) for ne in needs_e)
            obs.count("executor.lanes", int(sum(int(n.sum()) for n in self._last_needs)))
            result = out[:-2]
            return result[0] if self.agg == "count" else result
        raise RuntimeError(
            f"frontier overflow persists after {self.max_retries} retries: {chain}"
        )

    def _node_feedback_specs(self):
        """Per stage, per executed node: the (alias, consumed-vars) multiset
        whose joined cardinality that node's need_expand measures — or None
        when the measurement is not a joined-prefix size. Two exclusions:
        a cover that re-binds an already-bound variable (the executor
        semijoins AFTER expanding, so the count is pre-equate), and a stage
        alias whose consumed prefix is not the stage's full head (device-
        only output, no base-relation equivalent). A fully-consumed stage
        alias substitutes its own atoms' full specs, recursively, so every
        recorded spec names only base relations."""
        names = {n for n, _ in self.stages}
        full_specs: dict[str, tuple | None] = {}
        heads = {name: frozenset(p.query.head) for name, p in self.stages}
        out = []
        for (name, plan), sched in zip(self.stages, self.schedules):
            aliases = {sa.alias for node in plan.nodes for sa in node}
            prefix: dict[str, tuple[str, ...]] = {a: () for a in aliases}
            bound: set[str] = set()
            per_node = []
            for _k, cover, probes in sched.entries:
                rebinds = bool(set(cover.vars) & bound)
                prefix[cover.alias] = prefix[cover.alias] + tuple(cover.vars)
                bound |= set(cover.vars)
                spec: list | None = None if rebinds else []
                if spec is not None:
                    for a, vs in prefix.items():
                        if not vs:
                            continue
                        if a in names or a.startswith("__stage"):
                            # "__stage" but not in names: the hybrid path's
                            # per-call host materialization — never recorded
                            sub = (
                                full_specs.get(a)
                                if frozenset(vs) == heads.get(a)
                                else None
                            )
                            if sub is None:
                                spec = None
                                break
                            spec.extend(sub)
                        else:
                            spec.append((a, frozenset(vs)))
                per_node.append(tuple(spec) if spec else None)
                for sa in probes:
                    prefix[sa.alias] = prefix[sa.alias] + tuple(sa.vars)
                    bound |= set(sa.vars)
            out.append(tuple(per_node))
            fs: list | None = []
            for a in plan.query.atoms:
                if a.alias in names or a.alias.startswith("__stage"):
                    sub = full_specs.get(a.alias)
                    if sub is None:
                        fs = None
                        break
                    fs.extend(sub)
                else:
                    fs.append((a.alias, frozenset(a.vars)))
            full_specs[name] = tuple(fs) if fs else None
        return tuple(out)

    def _record_feedback(self, relations) -> None:
        """Persist the last call's measured expansion needs into the
        process-wide measured-cardinality store (relcache.FEEDBACK). Only
        meaningful measurements land: kill-mode filtered runs are skipped
        by the caller (lane counts depend on the constants; mask-mode
        batched runs keep the unfiltered layout and are safe), and nodes
        with no recordable prefix spec or a zero need (the factorized-count
        shortcut never expands) are skipped here."""
        from repro.core import relcache

        if self._last_needs is None:
            return
        if self._feedback_specs is None:
            self._feedback_specs = self._node_feedback_specs()
        for per_node, needs in zip(self._feedback_specs, self._last_needs):
            for spec, n in zip(per_node, np.asarray(needs)):
                if spec is None or int(n) <= 0:
                    continue
                relcache.FEEDBACK.record(
                    [(relations[a], vs) for a, vs in spec], int(n)
                )

    def run_relations(self, relations, *, reuse_tries: bool = True, filter_consts=None):
        """Convenience: host relations in, host results out — the warm
        path. Device columns come from the per-relation registry (uploaded
        once per column object) and base tries from the cross-call
        TRIE_CACHE, so a stream of calls over the same relations performs
        zero builds after the first. reuse_tries=False bypasses the trie
        cache and rebuilds in-graph every call (the cold baseline the
        benchmarks time). A batched runner returns the per-lane results:
        a (B,) int64 count vector for agg="count", else a list of
        (cols, mult) pairs, one per lane.

        Successful runs feed the optimizer's measured-cardinality loop:
        each node's exact frontier need is recorded against the relation
        objects it joined (see _record_feedback), except kill-mode filtered
        runs, whose lane counts depend on the selection constants."""
        data = {}
        for a in sorted(_base_aliases(self.stages)):
            rel = relations[a]
            if reuse_tries:
                lo = self._alias_lops.get(a)
                if lo is not None:
                    data[a] = TRIE_CACHE.get(
                        rel, device_columns(rel), lo, impl=self.impl, budget=self.budget
                    )
                    continue
            # raw-column (in-graph build) path: a tombstoned relation must
            # contribute its live rows only, so feed the per-version live
            # snapshot — an unweighted in-graph build has no mult to kill
            # the dead rows with
            data[a] = device_columns(relcache.live_relation(rel))
        out = self(data, filter_consts)
        if not self.filter_vars or self.batch is not None:
            self._record_feedback(relations)
        if self.agg == "count":
            # explicit d2h: the count read-back is the warm path's only
            # result transfer (see the transfer-guard regression test)
            return count_value(obs.read(out, "fj.result.read"))
        if self.batch:
            bound, valid, mult = out
            return [
                materialize_compiled(
                    {v: a[b] for v, a in bound.items()}, valid[b], mult[b]
                )
                for b in range(self.batch)
            ]
        return materialize_compiled(*out)


def materialize_compiled(bound, valid, mult):
    """Strip padding lanes from an agg=None result: returns (cols, mult) as
    host numpy arrays over live rows only (the eager engine's contract —
    expand duplicate multiplicities with engine.materialize). Raises
    CountOverflowError where a live row's multiplicity was refused."""
    bound, valid, mult = obs.read((bound, valid, mult), "fj.result.read")
    v = np.asarray(valid)
    refused = int((np.asarray(mult)[v] == MULT_SAT).sum())
    if refused:
        obs.count("executor.refused_lanes", refused)
        raise CountOverflowError(f"{refused} rows' multiplicity reached 2**31 - 1")
    cols = {name: np.asarray(a)[v].astype(np.int64) for name, a in bound.items()}
    return cols, np.asarray(mult)[v].astype(np.int64)
