"""The main path's device programs, compiled for a described TPU v5e chip.

Nothing runs: each test lowers a program the engine dispatches on the chip,
at chip-sized shapes, and compiles it with the TPU compiler. That compiler
refuses what the CPU backend and Pallas interpret mode accept (gathers it
cannot lower, programs that do not fit the device's memory), and it is where
compile time goes on the chip. The topology is described inside a fixture,
never at import, and every test of this kind lives in this one file, so a
single test worker loads the TPU library.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import compiled_free_join
from repro.core.capacity import ChainCapacityPlan
from repro.core.compiled import (
    _LevelOps,
    _base_aliases,
    _build_trie_jit,
    _merge_append_jit,
    build_trie,
)
from repro.core.distributed import spmd_count_program

# chip-sized inputs: JOB's cast_info at datagen scale 100, LSQB's knows at
# SF 10 and its append bucket, and a frontier of 4M lanes per node
JOB_ROWS = 12_000_000
KNOWS_ROWS = 1_800_200
KNOWS_BUCKET = 1 << 21
FRONTIER = 1 << 22


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip is written to the persistent
        # cache but cannot be read back without the chip: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, sharding, s.dtype), tree)


def _trie_shapes(lops, n, budget=32):
    """A built StaticTrie's pytree as shapes: what the warm path passes."""
    flat = [v for lv in lops.levels for v in lv]
    cols = {v: jax.ShapeDtypeStruct((n,), jnp.int32) for v in flat}
    return jax.eval_shape(lambda c: build_trie(c, lops, impl="jnp", budget=budget), cols)


def test_trie_build_compiles(one_chip):
    """The cached build of a 12M-row many-to-many table, both levels
    probed, as the host dispatches it: the group structure over a given row
    order, and each level's hash-table slots over a given slot order. The
    sorts themselves are one shared program per size bucket (ops.lex_order),
    compiled here at 4096 rows: at 16M rows the same program takes the TPU
    compiler most of a minute."""
    from repro.kernels import ops

    lops = _LevelOps((("t",), ("p",)), (True, True))
    rows = _sds((JOB_ROWS,), one_chip)
    _build_trie_jit.lower(
        {"t": rows, "p": rows}, lops=lops, impl="jnp", budget=32, order=rows, tables=False,
    ).compile()
    cap = ops._next_pow2(JOB_ROWS)
    ops._assign_slots.lower(
        _sds((JOB_ROWS, 2), one_chip), rows, rows, cap=cap, budget=32
    ).compile()
    small = _sds((1 << 12,), one_chip)
    ops._lsd_pass.lower(small, small).compile()


def _warm_program(query, rels, one_chip):
    """The warm AdaptiveExecutor program of a query planned on small data,
    lowered at chip-sized tries (rows scaled up to JOB_ROWS at most) and
    capacities of FRONTIER lanes."""
    info = {}
    compiled_free_join(query, rels, agg="count", info=info)
    runner = info["runner"]
    chain = runner._as_chain(runner.cap_plan)
    chain = ChainCapacityPlan(
        names=chain.names,
        stages=tuple(
            dataclasses.replace(cp, capacities=(FRONTIER,) * len(cp.capacities))
            for cp in chain.stages
        ),
    )
    scale = JOB_ROWS / max(r.num_rows for r in rels.values())
    tries = {
        a: _on(_trie_shapes(runner._alias_lops[a], int(rels[a].num_rows * scale)), one_chip)
        for a in sorted(_base_aliases(runner.stages))
    }
    return runner._fn(chain).lower(tries).compile()


def test_job_star_executor_compiles(one_chip):
    from benchmarks import datagen

    name, q, rels = next(
        t for t in datagen.job_queries(datagen.job_tables(scale=0.02)) if t[0] == "q_star4_m2m"
    )
    assert _warm_program(q, rels, one_chip).memory_analysis() is not None


def test_lsqb_triangle_executor_compiles(one_chip):
    from benchmarks import datagen

    name, q, rels = next(
        t for t in datagen.lsqb_queries(datagen.lsqb_tables(sf=0.05)) if t[0] == "q1_triangle"
    )
    assert _warm_program(q, rels, one_chip).memory_analysis() is not None


def test_delta_merge_compiles(one_chip):
    """One append of 4096 edges into a cached, padded knows trie."""
    lops = _LevelOps((("b",), ("c",)), (True, True))
    old = _on(_trie_shapes(lops, KNOWS_BUCKET), one_chip)
    compiled = _merge_append_jit.lower(
        {v: old.cols[v] for v in ("b", "c")},
        _sds((KNOWS_BUCKET,), one_chip),
        old.sorted_cols,
        old.order,
        _sds((), one_chip),
        {v: _sds((4096,), one_chip) for v in ("b", "c")},
        _sds((4096,), one_chip),
        lops=lops,
        impl="jnp",
        budget=32,
        cap=KNOWS_BUCKET,
        has_mult=True,
    ).compile()
    assert compiled.memory_analysis() is not None


def test_spmd_count_compiles_for_four_chips(topo):
    """The hypercube count over a 4-chip mesh: per-shard tries in, one psum
    of the count (and a pmax of the needs) out."""
    from benchmarks import datagen
    from repro.core import binary2fj, factor
    from repro.core.compiled import _static_schedule

    name, q, rels = next(
        t for t in datagen.lsqb_queries(datagen.lsqb_tables(sf=0.05)) if t[0] == "q1_triangle"
    )
    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), ("data",))
    sharded = NamedSharding(mesh, PartitionSpec("data"))
    fj = factor(binary2fj(q.atoms, q))
    schedule = _static_schedule(fj)
    shard_rows = KNOWS_ROWS // 2  # a share of 2 on each of two vars halves every relation
    tries = {
        a: jax.tree.map(
            lambda s: _sds((4,) + s.shape, sharded, s.dtype),
            _trie_shapes(lops, shard_rows),
        )
        for a, lops in schedule.level_ops.items()
    }
    program = spmd_count_program(
        fj, (FRONTIER,) * len(schedule), schedule, mesh, "data", "jnp", tries
    )
    compiled = program.lower(tries).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
