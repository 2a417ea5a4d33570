"""Pallas TPU kernel: sorted-set intersection via batched binary search.

Generic Join's leading intersection (R1.x ∩ R2.x ∩ ...) iterates the
smallest relation and probes the others. When trie keys are kept sorted
(our build is sort-based), the probe can be a binary search instead of a
hash probe — fewer memory touches for small-to-medium tables and no table
construction at all. Free Join uses it for intersection-style nodes whose
probed levels are already sorted.

The search is a fixed-depth (ceil(log2(N))) loop of masked midpoint updates:
static control flow, fully vectorized across a QBLK tile of query lanes.
"""
from __future__ import annotations

import functools

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QBLK = 1024


def _bsearch_kernel(b_ref, a_ref, mask_ref, pos_ref, *, n: int, steps: int):
    a = a_ref[...]  # (QBLK,) queries
    b = b_ref[...]  # (n,) sorted table
    lo = jnp.zeros(a.shape, dtype=jnp.int32)
    hi = jnp.full(a.shape, n, dtype=jnp.int32)  # search in [lo, hi)
    for _ in range(steps):
        mid = (lo + hi) // 2
        midv = b[jnp.clip(mid, 0, n - 1)]
        go_right = jnp.logical_and(midv < a, mid < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, jnp.maximum(mid, lo))
    found = jnp.logical_and(lo < n, b[jnp.clip(lo, 0, n - 1)] == a)
    mask_ref[...] = found
    pos_ref[...] = jnp.where(found, lo, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersect_pallas(a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool):
    """a: (Q,) int32 queries (Q % QBLK == 0); b: (N,) sorted int32, N >= 1.
    Returns (mask, pos): membership of each a[i] in b and its index."""
    n = int(b.shape[0])
    steps = max(1, math.ceil(math.log2(n + 1)))
    q = a.shape[0]
    kernel = functools.partial(_bsearch_kernel, n=n, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=(q // QBLK,),
        in_specs=[
            pl.BlockSpec(b.shape, lambda i: (0,)),
            pl.BlockSpec((QBLK,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((QBLK,), lambda i: (i,)),
            pl.BlockSpec((QBLK,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q,), jnp.bool_),
            jax.ShapeDtypeStruct((q,), jnp.int32),
        ],
        interpret=interpret,
    )(b, a)
