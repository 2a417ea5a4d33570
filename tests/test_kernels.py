"""Per-kernel shape/dtype sweeps, Pallas (interpret) vs pure-jnp oracles."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,k,q", [(1, 1, 5), (17, 2, 64), (300, 3, 700), (1000, 1, 2048)])
@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_hash_probe_vs_oracle(n, k, q, impl, rng):
    keys = np.unique(rng.integers(0, 10**6, (2 * n, k)).astype(np.int32), axis=0)[:n]
    table = ops.build_table(jnp.asarray(keys))
    assert int(table.max_disp) < 32
    qs = np.vstack(
        [keys[rng.integers(0, len(keys), q // 2)],
         rng.integers(10**6, 2 * 10**6, (q - q // 2, k)).astype(np.int32)]
    )
    want = ref.hash_probe_ref(jnp.asarray(keys), jnp.asarray(qs))
    got = ops.probe(table, jnp.asarray(qs), impl=impl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _home(keys: np.ndarray, cap: int) -> np.ndarray:
    return np.asarray(ops.mix32(jnp.asarray(keys)) & (cap - 1))


def _long_run_layout(k: int):
    """One key at each home slot of a 48-slot run, one more at its first
    slot: max_disp 1, but a miss homed at the run's start meets no empty
    slot within the 32-slot budget. Queries: every key, and misses homed
    at the run's first and middle slots."""
    cap, start, run = 128, 20, 48  # cap = ops._next_pow2(run + 1)
    cand = np.stack([np.arange(1, 20_000, dtype=np.int32)] * k, axis=1)
    cand[:, 1:] = 7
    home = _home(cand, cap)
    picks = [np.flatnonzero(home == s)[0] for s in range(start, start + run)]
    picks.append(np.flatnonzero(home == start)[1])
    misses = np.concatenate(
        [np.flatnonzero(home == start)[2:12], np.flatnonzero(home == start + run // 2)[1:11]]
    )
    return cand[picks], np.vstack([cand[picks], cand[misses]])


def _padded_trie_level_layout(rng):
    """A trie's level-1 table keyed by (parent gid, c) over 4096 rows, pad
    rows included (load 0.5): the first row of each group holds its key, any
    other row i the unique dead key (-i-2, 0). Queries: every live key, a
    miss under each parent, and one dead-lane key repeated."""
    n, n_real = 4096, 3000
    a = np.concatenate([rng.integers(0, 300, n_real), np.full(n - n_real, -1)]).astype(np.int32)
    c = np.concatenate([rng.integers(0, 50, n_real), np.full(n - n_real, -1)]).astype(np.int32)
    order = np.lexsort((c, a))
    a, c = a[order], c[order]
    first_a = np.r_[True, a[1:] != a[:-1]]
    first_ac = first_a | np.r_[True, c[1:] != c[:-1]]
    gid = np.cumsum(first_a).astype(np.int32) - 1
    idx = np.arange(n, dtype=np.int32)
    keys = np.stack([np.where(first_ac, gid, -idx - 2), np.where(first_ac, c, 0)], axis=1)
    live = keys[first_ac]
    parents = np.unique(gid)
    miss = np.stack([parents, np.full_like(parents, 99)], axis=1)
    dead = np.tile(np.array([[-1, 0]], np.int32), (2000, 1))
    return keys.astype(np.int32), np.vstack([live, miss, dead]).astype(np.int32)


@pytest.mark.parametrize("layout", ["long_run_k1", "long_run_k2", "padded_trie_level"])
def test_hash_probe_jnp_table_layouts_vs_oracle(layout, rng):
    if layout == "padded_trie_level":
        keys, qs = _padded_trie_level_layout(rng)
        assert ops._next_pow2(len(keys)) == 2 * len(keys)  # load 0.5
    else:
        keys, qs = _long_run_layout(int(layout[-1]))
    table = ops.build_table(jnp.asarray(keys))
    if layout != "padded_trie_level":
        assert int(table.max_disp) == 1
        assert int((np.asarray(table.slots) >= 0).sum()) == len(keys) > 32
    want = ref.hash_probe_ref(jnp.asarray(keys), jnp.asarray(qs))
    got = ops.probe(table, jnp.asarray(qs), impl="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bound", [0, 1, 2, None])
def test_probe_reads_the_tables_max_disp(bound, rng):
    """The jnp probe reads slots h .. h + table.max_disp and no further:
    with the bound forced lower, exactly the keys displaced past it are
    lost; with the table's own bound, every key is found."""
    n = 2048
    keys = np.unique(rng.integers(0, 10**6, (2 * n, 2)).astype(np.int32), axis=0)[:n]
    table = ops.build_table(jnp.asarray(keys))
    slots = np.asarray(table.slots)
    slot_of = np.empty(n, np.int64)
    slot_of[slots[slots >= 0]] = np.flatnonzero(slots >= 0)
    disp = slot_of - _home(keys, ops._next_pow2(n))
    assert disp.min() == 0 and disp.max() == int(table.max_disp)
    if bound is None:
        want = np.arange(n)
    else:
        assert (disp > bound).any() and (disp <= bound).any()
        table = table._replace(max_disp=jnp.int32(bound))
        want = np.where(disp <= bound, np.arange(n), -1)
    got = ops.probe(table, jnp.asarray(keys), impl="jnp")
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n", [1, 300, 5000])
def test_build_table_host_sort_same_table(n, rng):
    """Both home-slot sorts are stable, so the radix kernel and lex_order
    lay out the same table."""
    keys = np.unique(rng.integers(0, 10**6, (2 * n, 2)).astype(np.int32), axis=0)[:n]
    keys = jnp.asarray(keys)
    radix, host = ops.build_table(keys), ops.build_table(keys, host_sort=True)
    np.testing.assert_array_equal(np.asarray(host.slots), np.asarray(radix.slots))
    assert int(host.max_disp) == int(radix.max_disp)


@pytest.mark.parametrize("n", [0, 1, 1024, 1025, 5000])
def test_lex_order_is_stable_lexsort(n, rng):
    """Signed int32 keys with many ties, int32 max among them (the pad
    value of the size bucket): the order numpy's stable lexsort returns."""
    lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
    cols = [rng.integers(-3, 4, n).astype(np.int32), rng.choice([lo, -1, 0, 7, hi], n).astype(np.int32)]
    got = ops.lex_order([jnp.asarray(c) for c in cols])
    np.testing.assert_array_equal(np.asarray(got), np.lexsort(cols[::-1]))


def test_sort_bucket_is_next_power_of_two():
    assert [ops._sort_bucket(n) for n in (0, 1, 1024, 1025, 5000, 12_000_000)] == [
        1024, 1024, 1024, 2048, 8192, 1 << 24,
    ]


@pytest.mark.parametrize("m,n", [(1, 1), (100, 37), (1000, 999)])
@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_intersect_vs_oracle(m, n, impl, rng):
    b = np.unique(rng.integers(0, 10**5, n).astype(np.int32))
    a = np.concatenate(
        [
            b[rng.integers(0, len(b), m // 2 + 1)],
            rng.integers(10**5, 2 * 10**5, m // 2).astype(np.int32),
        ]
    )
    wm, wp = ref.intersect_ref(jnp.asarray(a), jnp.asarray(b))
    gm, gp = ops.intersect_sorted(jnp.asarray(a), jnp.asarray(b), impl=impl)
    np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp))


@pytest.mark.parametrize("g,f,cap", [(5, 8, 1024), (50, 100, 2048)])
@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_csr_expand_vs_oracle(g, f, cap, impl, rng):
    counts = rng.integers(0, 7, g).astype(np.int32)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    groups = rng.integers(0, g, f).astype(np.int32)
    wfr, wm, wv, wt = ref.csr_expand_ref(jnp.asarray(offsets), jnp.asarray(groups), cap)
    gfr, gm, gv, gt = ops.csr_expand_capped(
        jnp.asarray(offsets), jnp.asarray(groups), cap, impl=impl
    )
    np.testing.assert_array_equal(np.asarray(gfr), np.asarray(wfr))
    np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))
    assert int(gt) == int(wt)


def test_expand_counted_zero_counts():
    base = jnp.asarray(np.array([0, 5, 9], np.int32))
    counts = jnp.asarray(np.array([2, 0, 3], np.int32))
    fr, member, valid, total = ops.expand_counted(base, counts, 8)
    assert int(total) == 5
    np.testing.assert_array_equal(np.asarray(fr[:5]), [0, 0, 2, 2, 2])
    np.testing.assert_array_equal(np.asarray(member[:5]), [0, 1, 9, 10, 11])


@pytest.mark.parametrize(
    "n,doms", [(1, (4,)), (64, (16, 300)), (1000, (7, 5, 900)), (4096, (2, 2))]
)
@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_segmented_sort_vs_lexsort(n, doms, impl, rng):
    from repro.kernels.radix_sort import segmented_sort

    cols = [rng.integers(0, d, n).astype(np.int32) for d in doms]
    bits = tuple(max(1, int(d - 1).bit_length()) for d in doms)
    want = ref.segmented_sort_ref(cols)
    got = segmented_sort([jnp.asarray(c) for c in cols], bits, impl=impl)
    # stable LSD passes within refining segments reproduce the exact
    # lexsort permutation, not just the grouping
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_segmented_sort_presorted_prefix(impl, rng):
    """Seeding with a cached prefix order (the trie cache's order sharing)
    must land on the same permutation as the full sort."""
    from repro.kernels.radix_sort import segmented_sort

    n = 777
    c0 = jnp.asarray(rng.integers(0, 30, n).astype(np.int32))
    c1 = jnp.asarray(rng.integers(0, 500, n).astype(np.int32))
    full = segmented_sort([c0, c1], (5, 9), impl=impl)
    pre = segmented_sort([c0], (5,), impl=impl)
    seeded = segmented_sort([c0, c1], (5, 9), impl=impl, init_order=pre, presorted=1)
    np.testing.assert_array_equal(np.asarray(seeded), np.asarray(full))
    # a donor sorted by MORE vars: everything is presorted, zero passes
    both = segmented_sort([c0, c1], (5, 9), impl=impl, init_order=full, presorted=2)
    np.testing.assert_array_equal(np.asarray(both), np.asarray(full))


def test_segmented_sort_duplicate_heavy(rng):
    from repro.kernels.radix_sort import segmented_sort

    n = 2048
    cols = [np.zeros(n, np.int32), rng.integers(0, 3, n).astype(np.int32)]
    want = ref.segmented_sort_ref(cols)
    got = segmented_sort([jnp.asarray(c) for c in cols], (1, 2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_build_table_adversarial_same_slot(rng):
    # many keys whose mixed hash collides in low bits is handled by probing
    keys = (np.arange(512, dtype=np.int32) * 64)[:, None]
    t = ops.build_table(jnp.asarray(keys))
    got = ops.probe(t, jnp.asarray(keys))
    np.testing.assert_array_equal(np.asarray(got), np.arange(512))
