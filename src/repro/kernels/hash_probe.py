"""Pallas TPU kernel: batched open-addressing hash-table probe.

This is the hot loop of Free Join: every plan node probes each non-cover
relation's trie level with the whole frontier as one batch (Sec 4.3 taken to
its vector-hardware limit). The table is built once (sort + associative-scan
slot assignment, see ops.build_table) and probed many times, so the probe is
the kernel.

Layout: `slots` is a flat int32 array of length cap + PROBE_BUDGET; slots[s]
holds a row index into `table_keys` (or -1 = empty). A query key with home
slot h = mix(key) & (cap-1) lives within PROBE_BUDGET slots of h (linear
probing, no wrap — the tail margin absorbs the last cluster). The kernel
does PROBE_BUDGET unrolled gather+compare steps per query tile; each step is
a VMEM vector gather plus K int32 compares, so the whole probe is
memory-regular and MXU-free — ideal VPU work.

Tiling: queries are tiled (QBLK, K) in VMEM; the table (slots + key rows)
is resident in VMEM per block. For tables beyond VMEM the caller shards the
table (hash-partitioned) across the mesh instead — see core/distributed.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PROBE_BUDGET = 32
QBLK = 1024

_C1 = -1640531527  # 0x9E3779B9: Knuth multiplicative (int32 wrap)
_C2 = -862048943  # 0xCC9E2D51: murmur3 c1


def mix32(cols2d: jnp.ndarray) -> jnp.ndarray:
    """Mix (N, K) int32 key rows into int32 hashes (rows -> lanes).
    Constants are Python ints so the function is safe inside Pallas
    kernel bodies (no captured device arrays)."""
    h = jnp.full(cols2d.shape[:-1], 374761393, dtype=jnp.int32)
    k = cols2d.shape[-1]
    for i in range(k):
        c = cols2d[..., i]
        h = (h ^ (c * _C2)) * _C1
        h = h ^ (jax.lax.shift_right_logical(h, 15))
    return h


def _probe_kernel(slots_ref, tkeys_ref, q_ref, out_ref, *, cap: int, budget: int):
    q = q_ref[...]  # (QBLK, K)
    h = mix32(q) & (cap - 1)  # (QBLK,)
    slots = slots_ref[...]
    tkeys = tkeys_ref[...]
    nkeys = tkeys.shape[0]

    # rolled probe loop (fori_loop, not Python unrolling): the unrolled
    # gather chain triggers multi-minute XLA compiles at some table shapes
    # (seen in interpret mode on CPU); trip count is still the static budget
    def step(p, carry):
        res, done = carry
        cand = slots[h + p]  # VMEM vector gather
        is_empty = cand < 0
        krow = tkeys[jnp.clip(cand, 0, nkeys - 1)]  # (QBLK, K)
        match = jnp.logical_and(~is_empty, (krow == q).all(axis=-1))
        hit = jnp.logical_and(match, ~done)
        return jnp.where(hit, cand, res), done | hit | is_empty

    res = jnp.full(h.shape, -1, dtype=jnp.int32)
    done = jnp.zeros(h.shape, dtype=jnp.bool_)
    res, done = jax.lax.fori_loop(0, budget, step, (res, done))
    out_ref[...] = res


@functools.partial(jax.jit, static_argnames=("interpret",))
def hash_probe_pallas(
    slots: jnp.ndarray,
    table_keys: jnp.ndarray,
    query_keys: jnp.ndarray,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """slots: (cap + budget,) int32; table_keys: (N, K) int32 (N >= 1);
    query_keys: (Q, K) int32, Q % QBLK == 0. Returns (Q,) int32 row index
    or -1."""
    cap = slots.shape[0] - PROBE_BUDGET
    q = query_keys.shape[0]
    grid = (q // QBLK,)
    kernel = functools.partial(_probe_kernel, cap=cap, budget=PROBE_BUDGET)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(slots.shape, lambda i: (0,)),  # table resident
            pl.BlockSpec(table_keys.shape, lambda i: (0, 0)),
            pl.BlockSpec((QBLK, query_keys.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((QBLK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
        interpret=interpret,
    )(slots, table_keys, query_keys)
