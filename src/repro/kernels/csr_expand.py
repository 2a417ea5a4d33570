"""Pallas TPU kernel: capacity-bounded CSR expansion.

Free Join's cover iteration expands every frontier row into the members of
its trie sub-group (variable fan-out). On static-shape hardware the output
is a fixed-capacity buffer; each output slot finds its source frontier row
by binary search over the running prefix sum of fan-outs, then computes its
member offset. One gather-heavy, matmul-free pass — the write side of the
same VPU profile as hash_probe.

Inputs are precomputed outside the kernel: `starts` (exclusive prefix sum of
per-frontier-row counts) and `base` (each row's CSR segment start). The
kernel fills `capacity` output slots; slots >= total are -1.
"""
from __future__ import annotations

import functools

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

OBLK = 1024


def _expand_kernel(
    starts_ref, base_ref, total_ref, fr_ref, member_ref, *, f: int, steps: int, oblk: int
):
    i = pl.program_id(0)
    j = jax.lax.broadcasted_iota(jnp.int32, (oblk,), 0) + i * oblk
    starts = starts_ref[...]
    total = total_ref[0]
    # rightmost row with starts[row] <= j  (upper_bound - 1)
    lo = jnp.zeros(j.shape, dtype=jnp.int32)
    hi = jnp.full(j.shape, f, dtype=jnp.int32)
    for _ in range(steps):
        mid = (lo + hi) // 2
        midv = starts[jnp.clip(mid, 0, f - 1)]
        go_right = jnp.logical_and(midv <= j, mid < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, jnp.maximum(mid, lo))
    fr = jnp.clip(lo - 1, 0, f - 1)
    valid = j < total
    member = base_ref[...][fr] + (j - starts[fr])
    fr_ref[...] = jnp.where(valid, fr, -1)
    member_ref[...] = jnp.where(valid, member, -1)


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def csr_expand_pallas(
    starts: jnp.ndarray,
    base: jnp.ndarray,
    total: jnp.ndarray,
    *,
    capacity: int,
    interpret: bool,
):
    """starts/base: (F,) int32, F >= 1; total: (1,) int32.
    Returns (fr, member): each (capacity,) int32, -1 beyond total."""
    f = int(starts.shape[0])
    steps = max(1, math.ceil(math.log2(f + 1)))
    assert capacity % OBLK == 0
    kernel = functools.partial(_expand_kernel, f=f, steps=steps, oblk=OBLK)
    return pl.pallas_call(
        kernel,
        grid=(capacity // OBLK,),
        in_specs=[
            pl.BlockSpec(starts.shape, lambda i: (0,)),
            pl.BlockSpec(base.shape, lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((OBLK,), lambda i: (i,)),
            pl.BlockSpec((OBLK,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((capacity,), jnp.int32),
            jax.ShapeDtypeStruct((capacity,), jnp.int32),
        ],
        interpret=interpret,
    )(starts, base, total)
