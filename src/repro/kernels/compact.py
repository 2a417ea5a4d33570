"""Pallas TPU kernel: frontier compaction (prefix-sum scatter).

The compiled Free Join frontier is a fixed-capacity buffer with a valid
mask; probe misses kill lanes in place. Every dead lane is still carried
through all later expansions (cumsum, binary search, gathers all scale with
the *buffer* length, not the live count). When the live fraction drops, the
adaptive runner squeezes the frontier: output slot j is filled from the
(j+1)-th valid lane, so the live lanes land densely at the front of a
smaller buffer and every later node runs at the compacted capacity.

The scatter is expressed as a gather so each output slot is written exactly
once (no atomics): with `csum = cumsum(valid)` (inclusive, precomputed
outside the kernel like csr_expand's `starts`), the source lane of output
slot j is the leftmost i with csum[i] >= j+1 — one binary search per slot,
the same VPU profile as csr_expand.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CBLK = 1024


def _compact_kernel(csum_ref, live_ref, src_ref, *, n: int, steps: int, cblk: int):
    i = pl.program_id(0)
    j = jax.lax.broadcasted_iota(jnp.int32, (cblk,), 0) + i * cblk
    csum = csum_ref[...]
    live = live_ref[0]
    target = j + 1
    # leftmost i with csum[i] >= target (csum is non-decreasing)
    lo = jnp.zeros(j.shape, dtype=jnp.int32)
    hi = jnp.full(j.shape, n, dtype=jnp.int32)
    for _ in range(steps):
        mid = (lo + hi) // 2
        midv = csum[jnp.clip(mid, 0, n - 1)]
        open_ = lo < hi
        hi = jnp.where(open_ & (midv >= target), mid, hi)
        lo = jnp.where(open_ & (midv < target), mid + 1, lo)
    src_ref[...] = jnp.where(j < live, jnp.clip(lo, 0, n - 1), -1)


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def compact_pallas(
    csum: jnp.ndarray,
    live: jnp.ndarray,
    *,
    capacity: int,
    interpret: bool,
) -> jnp.ndarray:
    """csum: (N,) int32 inclusive prefix sum of the valid mask, N >= 1;
    live: (1,) int32 == csum[-1]. Returns src: (capacity,) int32 source lane
    of each output slot, -1 beyond live."""
    n = int(csum.shape[0])
    steps = max(1, math.ceil(math.log2(n + 1)))
    assert capacity % CBLK == 0
    kernel = functools.partial(_compact_kernel, n=n, steps=steps, cblk=CBLK)
    return pl.pallas_call(
        kernel,
        grid=(capacity // CBLK,),
        in_specs=[
            pl.BlockSpec(csum.shape, lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((CBLK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((capacity,), jnp.int32),
        interpret=interpret,
    )(csum, live)
