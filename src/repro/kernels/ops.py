"""Jit'd wrappers around the Pallas kernels, plus the table build.

Three implementations per op, selected by `impl`:
  "jnp"               pure-jnp vectorized path (default on CPU; identical
                      math to the kernel, XLA-fused)
  "pallas_interpret"  the Pallas kernel body executed in interpret mode
                      (CPU correctness validation of the TPU kernel)
  "pallas"            compiled Pallas (TPU target)

The hash-table *build* is sort-based and stays in jnp by design: slot
assignment after sorting by home slot is `slot_i = i + cummax(h_i - i)`
(an associative scan); there is no tiling decision for a kernel to make.
The probe is where the kernel earns its keep (many probes per build,
VPU-bound).

Every build sorts with XLA's sort, which runs fast on a TPU; but the TPU
compiler takes 20-60 s for every program that holds a sort of a few 100k
rows or more. A build dispatched from the host therefore sorts through
`lex_order`: one standalone sort program per power-of-two size bucket,
shared by every build and kept by the persistent compile cache. A build
traced inside a larger program (an executor's stage-output trie, an SPMD
shard) sorts inline, and that program pays the sort's compile once.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.compact import CBLK, compact_pallas
from repro.kernels.csr_expand import OBLK, csr_expand_pallas
from repro.kernels.hash_probe import PROBE_BUDGET, QBLK, hash_probe_pallas, mix32
from repro.kernels.intersect import intersect_pallas
from repro.kernels.radix_sort import (  # noqa: F401  (impl trio inside)
    lex_searchsorted,
    segmented_sort,
)
from repro.kernels.scan import cummax, cumsum


class Table(NamedTuple):
    slots: jnp.ndarray  # (cap + PROBE_BUDGET,) int32 row index or -1
    keys: jnp.ndarray  # (N, K) int32 key rows
    max_disp: jnp.ndarray  # () int32: max distance of a key from its home slot


def _next_pow2(n: int) -> int:
    return max(8, 1 << (max(1, 2 * n) - 1).bit_length())


_INT32_MAX = 2**31 - 1


def _sort_bucket(n: int) -> int:
    """Padded length of a `lex_order` sort: the next power of two, at
    least 1024. The same rule on every backend; each bucket compiles once
    per machine (the persistent compile cache keeps it)."""
    return max(1024, 1 << max(0, n - 1).bit_length())


@jax.jit
def _lsd_pass(col, perm):
    """One least-significant-key pass: reorder `perm` stably by col[perm].
    The position as second key makes every key pair unique, so the
    unstable two-operand sort (the cheapest to compile) returns the stable
    order."""
    with jax.named_scope("sort"):
        pos = jnp.arange(perm.shape[0], dtype=jnp.int32)
        return perm[jax.lax.sort((col[perm], pos), num_keys=2, is_stable=False)[1]]


def lex_order(cols: list[jnp.ndarray]) -> jnp.ndarray:
    """Stable lexicographic row order of int32 device columns (cols[0]
    major): the order jnp.lexsort(cols[::-1]) returns. Call it from the
    host, not inside a trace. It pads to `_sort_bucket` rows (pads sort
    last) and runs one pass per column through `_lsd_pass`, the same
    program for every column, table and relation of a size bucket."""
    from repro.core import obs  # deferred: repro.core imports this module

    n = int(cols[0].shape[0])
    b = _sort_bucket(n)
    with obs.span("fj.trie.lex_order"):
        perm = jnp.arange(b, dtype=jnp.int32)
        for c in reversed(cols):
            c = c.astype(jnp.int32)
            if b != n:
                c = jnp.concatenate([c, jnp.full(b - n, _INT32_MAX, jnp.int32)])
            perm = _lsd_pass(c, perm)
        return perm[:n]


@functools.partial(jax.jit, static_argnames=("cap",))
def _home_slots(keys: jnp.ndarray, cap: int) -> jnp.ndarray:
    with jax.named_scope("table"):
        return mix32(keys) & (cap - 1)


@functools.partial(jax.jit, static_argnames=("cap", "budget"))
def _assign_slots(keys, h, order, cap: int, budget: int) -> Table:
    """Linear-probing slots for rows taken in home-slot order."""
    with jax.named_scope("table"):
        n = keys.shape[0]
        hs = h[order]
        disp = cummax(hs - jnp.arange(n, dtype=jnp.int32))
        slot = jnp.arange(n, dtype=jnp.int32) + disp
        max_disp = (slot - hs).max(initial=0)
        slots = jnp.full(cap + budget, -1, dtype=jnp.int32)
        # slot is strictly increasing, so this is a sorted scatter (an
        # unsorted one makes the TPU compiler sort its indices)
        slots = slots.at[slot].set(
            order, mode="drop", indices_are_sorted=True, unique_indices=True
        )
    return Table(slots=slots, keys=keys, max_disp=max_disp)


@functools.partial(jax.jit, static_argnames=("cap", "budget"))
def _build(keys: jnp.ndarray, cap: int, budget: int) -> Table:
    with jax.named_scope("table"):
        h = mix32(keys) & (cap - 1)
        return _assign_slots(keys, h, jnp.argsort(h).astype(jnp.int32), cap, budget)


def build_table(
    keys: jnp.ndarray, budget: int = PROBE_BUDGET, *, host_sort: bool = False
) -> Table:
    """keys: (N, K) int32, rows unique. Linear probing, load factor <= 0.5,
    no wraparound (tail margin = `budget`). `max_disp`, the largest distance
    of a key from its home slot, bounds the jnp probe: it reads
    min(max_disp + 1, budget) slots per query. max_disp >= budget would mean
    an overflow — astronomically unlikely at <=0.5 load; checked by callers
    in tests via table.max_disp. Smaller budgets shrink the Pallas probe's
    static loop (§Perf J1) at the cost of a tighter displacement margin.

    The rows are sorted by home slot inline, as a caller tracing a larger
    program needs; host_sort=True (a caller on the host, outside any trace)
    sorts them with the shared `lex_order` programs instead."""
    if keys.ndim != 2:
        raise ValueError("keys must be (N, K)")
    cap = _next_pow2(keys.shape[0])
    if not host_sort:
        return _build(keys, cap, budget)
    h = _home_slots(keys, cap)
    return _assign_slots(keys, h, lex_order([h]), cap, budget)


@functools.partial(jax.jit, static_argnames=("budget",))
def _probe_jnp(slots, keys, queries, max_disp, budget: int):
    # rolled as a while_loop, not a Python loop: XLA's CPU pipeline hits
    # multi-minute compiles on the 32x-unrolled gather chain at some small
    # shapes (run the tier-1 suite at 17 keys / 64 queries to reproduce);
    # the rolled loop compiles in milliseconds. Trip count: the build put
    # every key at most `max_disp` slots past its home slot, so slots
    # h .. h + max_disp hold the answer for hits and misses alike, and the
    # loop reads exactly min(max_disp + 1, budget) of them. Rows are unique,
    # so at most one of those slots matches. `budget` stays the hard cap: an
    # overflowing table (max_disp >= budget) reads the whole margin.
    cap = slots.shape[0] - budget
    h = mix32(queries) & (cap - 1)
    nkeys = keys.shape[0]
    last = jnp.minimum(max_disp, budget - 1)

    def cond(state):
        p, _res = state
        return p <= last

    def body(state):
        p, res = state
        cand = slots[h + p]
        krow = keys[jnp.clip(cand, 0, nkeys - 1)]
        match = (cand >= 0) & (krow == queries).all(axis=-1)
        return p + 1, jnp.where(match, cand, res)

    init = (jnp.int32(0), jnp.full(h.shape, -1, dtype=jnp.int32))
    _, res = jax.lax.while_loop(cond, body, init)
    return res


def _pad_rows(x: jnp.ndarray, mult: int, fill) -> tuple[jnp.ndarray, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, width, constant_values=fill)
    return x, n


def probe(table: Table, queries: jnp.ndarray, impl: str = "jnp") -> jnp.ndarray:
    """queries: (Q, K) int32 -> (Q,) int32 row index in table.keys or -1."""
    if table.keys.shape[0] == 0 or queries.shape[0] == 0:
        return jnp.full(queries.shape[0], -1, dtype=jnp.int32)
    if impl == "jnp":
        budget = table.slots.shape[0] - _next_pow2(table.keys.shape[0])
        return _probe_jnp(table.slots, table.keys, queries, table.max_disp, budget)
    q, n = _pad_rows(queries, QBLK, 0)
    out = hash_probe_pallas(table.slots, table.keys, q, interpret=impl == "pallas_interpret")
    return out[:n]


def intersect_sorted(a: jnp.ndarray, b: jnp.ndarray, impl: str = "jnp"):
    """a: (Q,) queries; b: (N,) sorted unique. Returns (mask, pos)."""
    if b.shape[0] == 0 or a.shape[0] == 0:
        return jnp.zeros(a.shape[0], bool), jnp.full(a.shape[0], -1, jnp.int32)
    if impl == "jnp":
        pos = jnp.searchsorted(b, a).astype(jnp.int32)
        mask = (pos < b.shape[0]) & (b[jnp.clip(pos, 0, b.shape[0] - 1)] == a)
        return mask, jnp.where(mask, pos, -1)
    ap, n = _pad_rows(a, QBLK, 0)
    mask, pos = intersect_pallas(ap, b, interpret=impl == "pallas_interpret")
    return mask[:n], pos[:n]


def expand_counted(
    base: jnp.ndarray,
    counts: jnp.ndarray,
    capacity: int,
    impl: str = "jnp",
):
    """Variable-fanout expansion: frontier row i contributes `counts[i]`
    outputs, the j-th reading position base[i] + j. Returns
    (fr, member, valid, total) with static `capacity`. Rows with count 0
    (e.g. invalid frontier slots) contribute nothing."""
    counts = counts.astype(jnp.int32)
    cum = cumsum(counts)
    total = (cum[-1] if counts.shape[0] else jnp.int32(0)).astype(jnp.int32)
    starts = (cum - counts).astype(jnp.int32)
    base = base.astype(jnp.int32)
    if impl == "jnp":
        out = jnp.arange(capacity, dtype=jnp.int32)
        fr = jnp.searchsorted(starts, out, side="right").astype(jnp.int32) - 1
        fr = jnp.clip(fr, 0, max(counts.shape[0] - 1, 0))
        member = base[fr] + (out - starts[fr])
        valid = out < total
        return jnp.where(valid, fr, -1), jnp.where(valid, member, -1), valid, total
    cap = capacity + ((-capacity) % OBLK)
    fr, member = csr_expand_pallas(
        starts, base, total[None], capacity=cap, interpret=impl == "pallas_interpret"
    )
    valid = jnp.arange(cap, dtype=jnp.int32) < total
    return fr[:capacity], member[:capacity], valid[:capacity], total


def compact_indices(
    valid: jnp.ndarray,
    out_capacity: int,
    impl: str = "jnp",
):
    """Frontier compaction: squeeze the lanes where `valid` is True densely
    into the front of a buffer of `out_capacity` slots. Returns (src, live):
    src[j] is the source lane of output slot j (-1 beyond the live count),
    live is the number of valid lanes. Overflow iff live > out_capacity —
    detected by the caller, never silent (mirrors expand_counted)."""
    n = valid.shape[0]
    if n == 0:
        return jnp.full(out_capacity, -1, jnp.int32), jnp.int32(0)
    csum = cumsum(valid.astype(jnp.int32))
    live = csum[-1].astype(jnp.int32)
    if impl == "jnp":
        out = jnp.arange(out_capacity, dtype=jnp.int32)
        src = jnp.searchsorted(csum, out + 1, side="left").astype(jnp.int32)
        src = jnp.clip(src, 0, n - 1)
        return jnp.where(out < live, src, -1), live
    cap = out_capacity + ((-out_capacity) % CBLK)
    src = compact_pallas(csum, live[None], capacity=cap, interpret=impl == "pallas_interpret")
    return src[:out_capacity], live


def csr_expand_capped(
    offsets: jnp.ndarray,
    groups: jnp.ndarray,
    capacity: int,
    impl: str = "jnp",
):
    """Expand CSR members of each groups[i] into a `capacity` buffer.
    Returns (fr, member, valid, total). offsets: (G+1,) int32; groups: (F,).
    """
    if groups.shape[0] == 0:
        z = jnp.full(capacity, -1, jnp.int32)
        return z, z, jnp.zeros(capacity, bool), jnp.int32(0)
    counts = (offsets[groups + 1] - offsets[groups]).astype(jnp.int32)
    cum = cumsum(counts)
    total = cum[-1].astype(jnp.int32)
    starts = (cum - counts).astype(jnp.int32)
    base = offsets[groups].astype(jnp.int32)
    if impl == "jnp":
        out = jnp.arange(capacity, dtype=jnp.int32)
        fr = jnp.searchsorted(starts, out, side="right").astype(jnp.int32) - 1
        fr = jnp.clip(fr, 0, groups.shape[0] - 1)
        member = base[fr] + (out - starts[fr])
        valid = out < total
        return (
            jnp.where(valid, fr, -1),
            jnp.where(valid, member, -1),
            valid,
            total,
        )
    cap = capacity + ((-capacity) % OBLK)
    fr, member = csr_expand_pallas(
        starts, base, total[None], capacity=cap, interpret=impl == "pallas_interpret"
    )
    valid = jnp.arange(cap, dtype=jnp.int32) < total
    return fr[:capacity], member[:capacity], valid[:capacity], total
