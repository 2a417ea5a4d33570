"""Dry-run the distributed Free Join engine itself on the production mesh.

Lowers + compiles the shard_map'd HyperCube count (local compiled Free Join
+ psum) for the triangle and clover queries on both production meshes,
sharding over the flattened device grid. Proves the paper-pillar program is
coherent at 512 chips, and records its roofline terms next to the LM cells.

  python -m repro.launch.dryrun_join [--multi-pod]
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import binary2fj, factor  # noqa: E402
from repro.core.compiled import make_count_fn  # noqa: E402
from repro.launch.dryrun import _cost, _memory, collective_bytes  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.relational.schema import clover_query, triangle_query  # noqa: E402

def lower_join(multi_pod: bool, rows_per_shard: int = 65536, cap: int = 1 << 20):
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = tuple(mesh.axis_names)  # flatten the whole grid into shards
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    out = []
    for q in (triangle_query(), clover_query()):
        fj = factor(binary2fj(q.atoms, q))
        local = make_count_fn(fj, [cap] * 4, impl="jnp")

        def per_shard(cols):
            cols = jax.tree.map(lambda x: x[0], cols)
            c, ovf = local(cols)
            # count + overflow flag psum'd separately: no sentinel can ever
            # reach the caller (mirrors distributed.spmd_count's contract)
            return jax.lax.psum(c, axes), jax.lax.psum(ovf.astype(jnp.int32), axes)

        cols_sds = {
            a.alias: {
                v: jax.ShapeDtypeStruct((nshards, rows_per_shard), jnp.int32)
                for v in a.vars
            }
            for a in q.atoms
        }
        spec = P(axes)
        with mesh:
            fn = jax.jit(
                jax.shard_map(
                    per_shard,
                    mesh=mesh,
                    in_specs=(jax.tree.map(lambda _: spec, cols_sds),),
                    out_specs=(P(), P()),
                    # the probe's while_loop, bounded by each shard's own
                    # table, has no replication rule; outputs are
                    # explicitly psum-reduced
                    check_vma=False,
                )
            )
            t0 = time.time()
            compiled = fn.lower(cols_sds).compile()
            dt = time.time() - t0
        cost = _cost(compiled)
        rec = {
            "query": str(q),
            "multi_pod": multi_pod,
            "shards": nshards,
            "rows_per_shard": rows_per_shard,
            "compile_s": round(dt, 1),
            "flops_per_device": cost.get("flops"),
            "bytes_per_device": cost.get("bytes accessed"),
            "collective_bytes": collective_bytes(compiled.as_text()),
            "memory": _memory(compiled),
        }
        out.append(rec)
        print(
            f"[ok] join dry-run {q} shards={nshards} flops/dev={rec['flops_per_device']:.3e} "
            f"coll={sum(rec['collective_bytes'].values()):.3e}B compile={dt:.1f}s"
        )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun_join.json")
    args = ap.parse_args()
    recs = lower_join(args.multi_pod)
    existing = []
    if os.path.exists(args.out):
        existing = json.load(open(args.out))
    with open(args.out, "w") as f:
        json.dump(existing + recs, f, indent=1)


if __name__ == "__main__":
    main()
