"""Prefix scans (running sum, running max) that compile quickly for a TPU.

On a TPU, jnp.cumsum and lax.cummax lower to one reduce_window over the
whole axis, and the TPU compiler's time for that grows with the length:
about 40 s for one 1M-element cumsum, compiling for a described v5e chip on
an 8-core host, where a trie build at 12M rows holds several.
jax.lax.associative_scan is slower still to compile. Here the axis is cut
into blocks of BLOCK; a Hillis-Steele scan runs inside every block (log2
BLOCK shifted combines), and the block totals take the same scan one level
up. That is O(log n) levels of elementwise work, which the same compiler
handles in about a second at 12M elements. Integer results are identical to
jnp.cumsum / lax.cummax.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

BLOCK = 128


def _shift(x, s: int, axis: int, fill):
    """x shifted by s along `axis`, the first s slots filled with `fill`."""
    head = jnp.full(x.shape[:axis] + (s,) + x.shape[axis + 1 :], fill, x.dtype)
    keep = [slice(None)] * x.ndim
    keep[axis] = slice(0, x.shape[axis] - s)
    return jnp.concatenate([head, x[tuple(keep)]], axis=axis)


def _in_block(x, axis: int, op, fill):
    """Hillis-Steele inclusive scan along `axis` (its length is one block)."""
    s = 1
    while s < x.shape[axis]:
        x = op(x, _shift(x, s, axis, fill))
        s *= 2
    return x


def _scan0(x, op, fill):
    """Inclusive scan along axis 0, blocked (see module docstring)."""
    n = x.shape[0]
    if n <= BLOCK:
        return _in_block(x, 0, op, fill)
    rest = x.shape[1:]
    pad = (-n) % BLOCK
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,) + rest, fill, x.dtype)])
    blocks = _in_block(x.reshape((-1, BLOCK) + rest), 1, op, fill)
    # every block combines with the scanned totals of the blocks before it
    before = _shift(_scan0(blocks[:, -1], op, fill), 1, 0, fill)
    return op(blocks, before[:, None]).reshape((-1,) + rest)[:n]


def _scan(x, axis: int, op, fill):
    x = jnp.asarray(x)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    if x.shape[axis] <= 1:
        return x
    if axis % x.ndim == 0:
        return _scan0(x, op, fill)
    if x.shape[axis] <= BLOCK:  # a short minor axis: one block, no reshape
        return _in_block(x, axis % x.ndim, op, fill)
    return jnp.moveaxis(_scan0(jnp.moveaxis(x, axis, 0), op, fill), 0, axis)


def cumsum(x, axis: int = 0):
    """Inclusive running sum along `axis` (== jnp.cumsum for integers;
    booleans count as int32)."""
    return _scan(x, axis, jnp.add, 0)


def cummax(x, axis: int = 0):
    """Inclusive running max along `axis` (== jax.lax.cummax)."""
    x = jnp.asarray(x)
    return _scan(x, axis, jnp.maximum, np.iinfo(x.dtype).min)
