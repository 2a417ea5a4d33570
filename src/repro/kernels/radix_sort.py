"""Pallas TPU kernel: segmented radix sort for lazy trie construction.

The compiled Free Join trie build needs rows grouped hierarchically by the
plan's level vars. A full-width comparison sort (jnp.lexsort over every
level var at once) pays N log N comparisons per var and re-sorts vars that
earlier levels already grouped; Worst-Case Optimal Radix Triejoin
(arXiv 1912.12747) observes that radix partitioning level-by-level is the
right primitive: at level d the rows are already contiguous within their
depth-(d-1) groups, so the level's var only has to be rank-ordered *inside
each parent segment* — a stable LSD counting sort over small digits whose
passes scale with the key width of that one var, not with the whole key
tuple.

One pass (digit width RBITS, radix R = 2**RBITS) over the current
permutation works on three precomputed arrays:

  digit[i]   the i-th row's current digit
  csum[i,r]  inclusive count of digit r among rows 0..i (a (N,R) cumsum)
  seg[i]     the row's parent segment id (non-decreasing: segments are
             contiguous runs of the current order)

and sends row i to
  dst[i] = seg_start + offset_of_digit_within_segment + rank_within(seg,digit)
— a permutation that never crosses segment boundaries, so the segment ids
survive every pass unchanged and stability gives the lexicographic order.

Like kernels/compact.py, the scatter is re-expressed as a gather so each
output slot is written exactly once: slot j knows its digit k_j and its
target rank t_j (precomputed outside the kernel from the per-segment digit
histograms), and its source row is the leftmost i with csum[i, k_j] >= t_j —
one binary search per slot, the same VPU profile as csr_expand. The jnp
variant keeps the scatter formulation (XLA fuses it); the Pallas kernel is
the gather.

Keys must be non-negative (join keys are dictionary-encoded int32 >= 0).

The engine's trie and table builds do not call this sort: they use XLA's
sort (ops.lex_order from the host, jnp.lexsort/argsort inside traced
programs). It stays for the kernel tests and benchmarks until the chip
decides between them. On CPU the jnp variant runs within ~2x of XLA's
comparison lexsort. On a TPU its unsorted scatter into more than ~2M slots
makes the compiler sort the indices (about 20 s of compile per program at
12M rows), and the Pallas kernel does not compile there yet.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

RBITS = 4
RADIX = 1 << RBITS
SBLK = 1024


def _rank_kernel(csum_ref, kd_ref, kt_ref, src_ref, *, n: int, steps: int):
    """src[j] = leftmost i with csum[i, kd[j]] >= kt[j] (csum columns are
    non-decreasing). One binary search per output slot."""
    csum = csum_ref[...]  # (n, R)
    kd = kd_ref[...]
    kt = kt_ref[...]
    lo = jnp.zeros(kd.shape, dtype=jnp.int32)
    hi = jnp.full(kd.shape, n, dtype=jnp.int32)
    for _ in range(steps):
        mid = (lo + hi) // 2
        midv = csum[jnp.clip(mid, 0, n - 1), kd]
        open_ = lo < hi
        hi = jnp.where(open_ & (midv >= kt), mid, hi)
        lo = jnp.where(open_ & (midv < kt), mid + 1, lo)
    src_ref[...] = jnp.clip(lo, 0, n - 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def radix_rank_pallas(
    csum: jnp.ndarray,
    kd: jnp.ndarray,
    kt: jnp.ndarray,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """csum: (N, R) int32 inclusive per-digit prefix counts; kd/kt: (N,)
    int32 digit and target rank per output slot (N % SBLK == 0 is padded
    here). Returns src: (N,) int32 source position of each output slot."""
    n = int(csum.shape[0])
    cap = n + ((-n) % SBLK)
    if cap != n:
        kd = jnp.pad(kd, (0, cap - n))
        kt = jnp.pad(kt, (0, cap - n))
    steps = max(1, math.ceil(math.log2(n + 1)))
    kernel = functools.partial(_rank_kernel, n=n, steps=steps)
    src = pl.pallas_call(
        kernel,
        grid=(cap // SBLK,),
        in_specs=[
            pl.BlockSpec(csum.shape, lambda i: (0, 0)),
            pl.BlockSpec((SBLK,), lambda i: (i,)),
            pl.BlockSpec((SBLK,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((SBLK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((cap,), jnp.int32),
        interpret=interpret,
    )(csum, kd, kt)
    return src[:n]


def _seg_starts(seg: jnp.ndarray) -> jnp.ndarray:
    """Per-row start position of the row's (contiguous) segment."""
    n = seg.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    first = jnp.zeros(n, dtype=bool).at[0].set(True)
    first = first.at[1:].set(seg[1:] != seg[:-1])
    # running max of the last segment-start position
    return jax.lax.cummax(jnp.where(first, idx, 0))


def _radix_pass(perm, starts, seg_last, digit: jnp.ndarray, impl: str):
    """One stable counting-sort pass of `perm` by `digit` within contiguous
    segments. `starts`/`seg_last` give each row's segment start/end position
    (invariant across the passes of one var — computed once by the caller).
    Returns the new permutation of positions (segments are preserved)."""
    n = perm.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    onehot = (digit[:, None] == jnp.arange(RADIX, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    csum = jnp.cumsum(onehot, axis=0)  # (N, R) inclusive per-digit counts
    pcs = jnp.cumsum(csum, axis=1)  # (N, R): rows <= j with digit <= r
    start1 = jnp.clip(starts - 1, 0, n - 1)
    at_start = starts > 0

    def upto(tbl, col):  # tbl[., col] restricted to the row's segment
        return tbl[seg_last, col] - jnp.where(at_start, tbl[start1, col], 0)

    if impl == "jnp":
        # every lookup is a per-row scalar gather — no (N, R) gathers
        within = csum[idx, digit] - jnp.where(at_start, csum[start1, digit], 0) - 1
        off = jnp.where(digit > 0, upto(pcs, jnp.maximum(digit - 1, 0)), 0)
        dst = starts + off + within
        src = jnp.zeros(n, jnp.int32).at[dst].set(idx)
        return perm[src]
    # gather formulation (the Pallas kernel): slot j's digit and target rank
    local = idx - starts  # position within the segment
    seg_pcs = pcs[seg_last] - jnp.where(at_start[:, None], pcs[start1], 0)  # (N, R)
    kd = jnp.sum((seg_pcs <= local[:, None]).astype(jnp.int32), axis=1).astype(jnp.int32)
    kd = jnp.clip(kd, 0, RADIX - 1)
    off = jnp.where(kd > 0, upto(pcs, jnp.maximum(kd - 1, 0)), 0)
    base = jnp.where(at_start, csum[start1, kd], 0)  # digit-kd rows before the segment
    kt = base + (local - off) + 1
    src = radix_rank_pallas(csum, kd, kt, interpret=impl == "pallas_interpret")
    return perm[src]


def _refine_segments(seg: jnp.ndarray, sorted_key: jnp.ndarray) -> jnp.ndarray:
    """New segment ids after a var is fully sorted: split each segment at
    every value change of the (now sorted-within-segment) key."""
    flag = jnp.zeros(seg.shape[0], dtype=bool).at[0].set(True)
    flag = flag.at[1:].set((seg[1:] != seg[:-1]) | (sorted_key[1:] != sorted_key[:-1]))
    return (jnp.cumsum(flag.astype(jnp.int32)) - 1).astype(jnp.int32)


def segmented_sort(
    cols: list[jnp.ndarray],
    key_bits: tuple[int, ...],
    impl: str = "jnp",
    init_order: jnp.ndarray | None = None,
    presorted: int = 0,
) -> jnp.ndarray:
    """Row permutation sorting `cols` lexicographically (cols[0] major), via
    per-var LSD radix passes inside the segments induced by earlier vars.

    key_bits[i] must cover cols[i]'s value range (values in [0, 2**bits));
    pass count per var is ceil(key_bits[i] / RBITS) — static, so the whole
    sort lowers under jit. `init_order` with `presorted=k` starts from a
    permutation already sorted by the first k cols (a shared prefix order
    from the trie cache): those vars pay only the segment refinement, never
    a sorting pass."""
    assert len(cols) == len(key_bits) and cols, "one key width per column"
    n = int(cols[0].shape[0])
    perm = (
        jnp.arange(n, dtype=jnp.int32)
        if init_order is None
        else init_order.astype(jnp.int32)
    )
    assert 0 <= presorted <= len(cols)
    assert presorted == 0 or init_order is not None, "presorted needs init_order"
    seg = jnp.zeros(n, jnp.int32)
    for ci, (col, bits) in enumerate(zip(cols, key_bits)):
        col = col.astype(jnp.int32)
        if ci >= presorted:
            starts = _seg_starts(seg)
            seg_last = (n - 1) - _seg_starts(seg[::-1])[::-1]  # last position
            for shift in range(0, max(1, int(bits)), RBITS):
                digit = (col[perm] >> shift) & (RADIX - 1)
                perm = _radix_pass(perm, starts, seg_last, digit, impl)
        seg = _refine_segments(seg, col[perm])
    return perm


def lex_searchsorted(
    sorted_cols: list[jnp.ndarray],
    query_cols: list[jnp.ndarray],
) -> jnp.ndarray:
    """Per-query insertion rank (side="left") of each query tuple into the
    lexicographically sorted rows of `sorted_cols` (cols[0] major).

    The merge half of the delta trie build: the delta's rows are sorted
    among themselves by `segmented_sort`, then this locates each one's slot
    in the cached sorted run — the splice positions of a sorted-run merge
    without a full re-sort. Same fixed-step binary-search shape as
    `_rank_kernel`: ceil(log2(N+1)) gather rounds, each lane masked once
    its bracket closes, so the whole search lowers under jit with static
    iteration count. Lexicographic "row < query" is folded from the least
    significant column backward: a < b at column d iff
    (a_d < b_d) | (a_d == b_d & a_{<d-suffix} < b-suffix).
    """
    assert sorted_cols and len(sorted_cols) == len(query_cols)
    n = int(sorted_cols[0].shape[0])
    q = query_cols[0].shape[0]
    if n == 0:
        return jnp.zeros(q, dtype=jnp.int32)

    def row_lt_query(pos):  # (Q,) bool: sorted row `pos[j]` < query j ?
        lt = jnp.zeros(pos.shape, dtype=bool)
        for sc, qc in zip(reversed(sorted_cols), reversed(query_cols)):
            a = sc.astype(jnp.int32)[pos]
            b = qc.astype(jnp.int32)
            lt = (a < b) | ((a == b) & lt)
        return lt

    lo = jnp.zeros(q, dtype=jnp.int32)
    hi = jnp.full(q, n, dtype=jnp.int32)
    for _ in range(max(1, math.ceil(math.log2(n + 1)))):
        mid = (lo + hi) // 2
        lt = row_lt_query(jnp.clip(mid, 0, n - 1))
        open_ = lo < hi
        lo = jnp.where(open_ & lt, mid + 1, lo)
        hi = jnp.where(open_ & ~lt, mid, hi)
    return lo
