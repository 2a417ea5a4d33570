"""Chip smoke: the join engine's main path, once, on a TPU.

    python chip_smoke.py [--seed 0] [--job-scale 100] [--lsqb-sf 10]
    python chip_smoke.py --chips 4

One process owns the chip and runs three phases through the entry points a
user calls, on data generated from --seed by benchmarks/datagen.py:

* analytics: compiled_free_join over every JOB-shaped query
  (datagen.job_queries at scale 100: 12M-row cast_info and movie_keyword),
  each cold and then warm, and the LSQB triangle and 4-cycle at SF 10
  (1.8M knows edges, the size of LDBC SNB SF10's person-knows-person);
* serving: a JoinServeEngine answering filtered requests from two tenants
  over two templates on the JOB tables, in batched dispatches;
* standing: a StandingQueryEngine holding the LSQB triangle count while
  append batches go into knows.

With --chips 4 only the hypercube count (SpmdCounter) of the LSQB triangle
runs, on a 4-chip mesh, against the one-device compiled count of the same
query in the same process.

Every answer is compared with the eager host engine (free_join). A wrong
answer, a missing TPU, or an answer from any rung of the degradation ladder
(eager fallback, halved or unbatched batch, an absorbed fault) exits
nonzero. Earlier output lines are one JSON object each; the last line is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(RuntimeError):
    """An answer disagreed with the reference or was not served by the
    compiled path."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def tpu_devices():
    """The TPU devices JAX sees; exits when it sees none, so nothing ever
    runs (or reports) on the CPU in the chip's place."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (jax.devices()[0].platform is "
            f"{devs[0].platform!r}); refusing to run on another backend"
        )
    return devs


def peak_bytes(devs) -> int:
    """Process-lifetime peak of device bytes in use, max over `devs`."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def rows_of(rels) -> dict:
    return {a: int(r.num_rows) for a, r in rels.items()}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def analytics_phase(devs, job_tables, lsqb_tables):
    """compiled_free_join over the JOB queries and the LSQB triangle and
    4-cycle, cold then warm, each against the eager count."""
    from benchmarks import datagen
    from repro.core import compiled_free_join, free_join, membudget

    queries = datagen.job_queries(job_tables)
    queries += [
        t for t in datagen.lsqb_queries(lsqb_tables) if t[0] in ("q1_triangle", "q3_square")
    ]
    while queries:
        # pop as we go: a finished query's relations (and their cached
        # tries and runners) are released before the next one builds
        name, q, rels = queries.pop(0)
        want, ref_s = timed(lambda q=q, rels=rels: free_join(q, rels, agg="count"))
        runs = []
        for _ in ("cold", "warm"):
            info: dict = {}
            got, dt = timed(
                lambda q=q, rels=rels, info=info: compiled_free_join(
                    q, rels, agg="count", info=info
                )
            )
            check(
                info.get("degraded_to") is None,
                f"{name}: answered by the eager fallback ({info.get('degraded_from')})",
            )
            check(got == want, f"{name}: compiled count {got} != eager count {want}")
            runs.append((got, dt, info))
        (_, cold_s, _), (got, warm_s, info) = runs
        emit(
            phase="analytics",
            query=name,
            rows=rows_of(rels),
            count=got,
            reference=want,
            cold_s=cold_s,
            warm_s=warm_s,
            eager_s=ref_s,
            retries=info["retries"],
            compiles=info["compiles"],
            peak_bytes_in_use=peak_bytes(devs),
            governor_peak_bytes=membudget.GOVERNOR.peak_bytes,
        )
        del rels, runs, info


def serving_templates(job_tables):
    """Two tenants' spellings of two templates over the JOB tables:
    keyword-type counts (movie_keyword x keyword, filter kw_type) and
    company-country title counts (movie_companies x company x title,
    filters country and kind)."""
    from repro.relational.relation import Relation
    from repro.relational.schema import Atom, Query

    t = job_tables["title"]
    title = Relation("title", {"t": t.columns["t"], "kind": t.columns["kind"]})
    mk, kw = job_tables["movie_keyword"], job_tables["keyword"]
    mc, co = job_tables["movie_companies"], job_tables["company"]
    templates = {}
    for tenant in ("tenant_a", "tenant_b"):
        keyword_q = Query(
            [
                Atom("movie_keyword", ("t", "k"), f"{tenant}_mk"),
                Atom("keyword", ("k", "kw_type"), f"{tenant}_kw"),
            ]
        )
        company_q = Query(
            [
                Atom("company", ("c", "country"), f"{tenant}_co"),
                Atom("movie_companies", ("t", "c"), f"{tenant}_mc"),
                Atom("title", ("t", "kind"), f"{tenant}_t"),
            ]
        )
        templates[tenant] = {
            "keyword_type": (
                keyword_q,
                {f"{tenant}_mk": mk, f"{tenant}_kw": kw},
            ),
            "company_titles": (
                company_q,
                {f"{tenant}_co": co, f"{tenant}_mc": mc, f"{tenant}_t": title},
            ),
        }
    return templates


def serving_phase(devs, job_tables, seed: int, n_requests: int = 32, slots: int = 8):
    """A few dozen filtered requests from two tenants over two templates,
    drained by one JoinServeEngine; every answer against the eager count."""
    import numpy as np

    from repro.core import free_join, membudget
    from repro.serve import JoinServeEngine

    rng = np.random.default_rng(seed)
    templates = serving_templates(job_tables)
    consts = {
        "keyword_type": [{"kw_type": int(c)} for c in range(5)],
        "company_titles": [
            {"country": int(c), "kind": int(k)} for c, k in ((0, 1), (3, 0), (7, 4), (11, 2))
        ],
    }
    trace = []
    for i in range(n_requests):
        tenant = ("tenant_a", "tenant_b")[i % 2]
        kind = ("keyword_type", "company_titles")[(i // 2) % 2]
        q, rels = templates[tenant][kind]
        filters = consts[kind][int(rng.integers(len(consts[kind])))]
        trace.append((tenant, kind, q, rels, filters))

    eng = JoinServeEngine(slots=slots)
    reqs = [eng.submit(q, rels, filters, tenant=tenant) for tenant, _k, q, rels, filters in trace]
    _, wall = timed(eng.run)
    references: dict = {}
    eager_s = 0.0
    for (_tenant, kind, q, rels, filters), req in zip(trace, reqs):
        check(req.done and req.error is None, f"serving {kind} {filters}: {req.error!r}")
        check(req.degraded_to is None, f"serving {kind} {filters}: served {req.degraded_to}")
        key = (kind, tuple(sorted(filters.items())))
        if key not in references:
            references[key], dt = timed(
                lambda q=q, rels=rels, filters=filters: free_join(
                    q, rels, agg="count", filters=filters
                )
            )
            eager_s += dt
        want = references[key]
        check(req.result == want, f"serving {kind} {filters}: {req.result} != eager {want}")
    check(not any(eng.degraded.values()), f"serving degraded: {eng.degraded}")
    check(eng.faults_absorbed == 0, f"serving absorbed {eng.faults_absorbed} faults")
    check(
        eng.dispatches < len(reqs),
        f"no batching: {eng.dispatches} dispatches for {len(reqs)} requests",
    )
    emit(
        phase="serving",
        requests=len(reqs),
        tenants=2,
        templates=2,
        slots=slots,
        dispatches=eng.dispatches,
        rows={
            "movie_keyword": int(job_tables["movie_keyword"].num_rows),
            "movie_companies": int(job_tables["movie_companies"].num_rows),
            "title": int(job_tables["title"].num_rows),
        },
        counts={f"{k} {dict(c)}": references[(k, c)] for k, c in references},
        drain_s=wall,
        eager_s=eager_s,
        peak_bytes_in_use=peak_bytes(devs),
        governor_peak_bytes=membudget.GOVERNOR.peak_bytes,
    )


def standing_phase(devs, lsqb_tables, seed: int, batches: int = 4, batch_edges: int = 4096):
    """The LSQB triangle count held by a StandingQueryEngine while append
    batches (random edges plus closed triangles) go into knows; the answer
    after every batch against the eager count over host copies."""
    import numpy as np

    from benchmarks import datagen
    from repro.core import free_join, membudget, relcache
    from repro.relational.relation import Relation
    from repro.serve import StandingQueryEngine

    rng = np.random.default_rng(seed + 7)
    name, q, rels = next(t for t in datagen.lsqb_queries(lsqb_tables) if t[0] == "q1_triangle")
    n_person = int(max(rels["K1"].columns["a"].max(), rels["K1"].columns["b"].max())) + 1
    src = rels["K1"].columns["a"].copy()
    dst = rels["K1"].columns["b"].copy()

    def reference():
        knows = Relation("knows", {"a": src, "b": dst})
        ref_rels = {
            "K1": knows,
            "K2": knows.rename({"a": "b", "b": "c"}),
            "K3": knows.rename({"a": "c", "b": "a"}),
        }
        return free_join(q, ref_rels, agg="count")

    eng = StandingQueryEngine()
    sq, register_s = timed(lambda: eng.register(q, rels, agg="count"))
    want = reference()
    check(sq.result == want, f"standing {name}: registered {sq.result} != eager {want}")
    counts, ingest_s = [sq.result], []
    for _ in range(batches):
        # a third of the batch closes triangles u->v->w->u, so the count moves
        tri = rng.integers(0, n_person, (batch_edges // 6, 3))
        a = np.concatenate([rng.integers(0, n_person, batch_edges - 3 * len(tri)), tri.ravel()])
        b = np.concatenate(
            [rng.integers(0, n_person, batch_edges - 3 * len(tri)), np.roll(tri, -1, 1).ravel()]
        )
        a, b = a.astype(src.dtype), b.astype(dst.dtype)
        # knows appears under three aliases: the renamed copies take the
        # batch first, and ingest() into K1 then refreshes the query once
        relcache.append(rels["K2"], {"b": a, "c": b})
        relcache.append(rels["K3"], {"c": a, "a": b})
        _, dt = timed(lambda a=a, b=b: eng.ingest(rels["K1"], {"a": a, "b": b}))
        ingest_s.append(dt)
        src, dst = np.concatenate([src, a]), np.concatenate([dst, b])
        want = reference()
        check(sq.result == want, f"standing {name}: {sq.result} != eager {want} after ingest")
        check(sq.degraded_to is None, f"standing {name}: served {sq.degraded_to}")
        counts.append(sq.result)
    check(eng.degraded_refreshes == 0, f"standing: {eng.degraded_refreshes} degraded refreshes")
    emit(
        phase="standing",
        query=name,
        rows={"knows_start": int(len(src) - batches * batch_edges), "knows_end": int(len(src))},
        batches=batches,
        batch_edges=batch_edges,
        count=counts[-1],
        reference=want,
        counts=counts,
        register_s=register_s,
        ingest_s=ingest_s,
        peak_bytes_in_use=peak_bytes(devs),
        governor_peak_bytes=membudget.GOVERNOR.peak_bytes,
    )


def spmd_phase(devs, lsqb_tables):
    """Hypercube count of the LSQB triangle over a mesh of every chip,
    against the one-device compiled count and the eager count."""
    import jax

    from benchmarks import datagen
    from repro.core import compiled_free_join, free_join
    from repro.core.distributed import SpmdCounter

    name, q, rels = next(t for t in datagen.lsqb_queries(lsqb_tables) if t[0] == "q1_triangle")
    want, eager_s = timed(lambda: free_join(q, rels, agg="count"))
    info: dict = {}
    one, one_s = timed(lambda: compiled_free_join(q, rels, agg="count", info=info))
    check(info.get("degraded_to") is None, f"{name}: one-device count degraded")
    runner = info["runner"]
    check(len(runner.stages) == 1, f"{name}: expected a one-stage plan, got {len(runner.stages)}")
    mesh = jax.make_mesh((len(devs),), ("data",))
    ctr, build_s = timed(lambda: SpmdCounter(q, rels, runner.plan, None, mesh))
    # each shard's fragment sits on its own chip, not all on the first
    for leaf in jax.tree.leaves(ctr._dense):
        check(len(leaf.sharding.device_set) == len(devs), f"{name}: fragments not sharded")
    got, cold_s = timed(ctr)
    warm, warm_s = timed(ctr)
    check(one == want, f"{name}: one-device count {one} != eager {want}")
    check(got == one and warm == one, f"{name}: spmd count {got}/{warm} != one-device {one}")
    emit(
        phase="spmd",
        query=name,
        rows=rows_of(rels),
        shards=len(devs),
        shares=ctr.shares,
        count=got,
        one_device_count=one,
        reference=want,
        partition_s=build_s,
        cold_s=cold_s,
        warm_s=warm_s,
        one_device_s=one_s,
        eager_s=eager_s,
        retries=ctr.retries,
        peak_bytes_in_use=peak_bytes(devs),
    )


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--job-scale", type=float, default=100.0)
    ap.add_argument("--lsqb-sf", type=float, default=10.0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the hypercube count on a 4-chip mesh",
    )
    args = ap.parse_args(argv)

    devs = tpu_devices()
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} device(s)")
    devs = devs[: args.chips]
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks import datagen
    from repro.compile_cache import enable_compile_cache

    emit(
        phase="config",
        platform=devs[0].platform,
        kind=devs[0].device_kind,
        chips=len(devs),
        seed=args.seed,
        job_scale=args.job_scale,
        lsqb_sf=args.lsqb_sf,
        cuts=[],  # no scale is cut: the sizes above fit one v5e chip and the time limit
        compile_cache=enable_compile_cache(),
    )
    with warnings.catch_warnings():
        # the standalone compiled path reports its eager fallback only as a
        # RuntimeWarning; here that is a failure, not a footnote
        warnings.filterwarnings(
            "error", message="compiled path degraded", category=RuntimeWarning
        )
        # the graph takes seed + 1, datagen's own offset between the two
        # generators; at the default seed its triangle and 4-cycle counts
        # are nonzero
        (lsqb, gen_s) = timed(lambda: datagen.lsqb_tables(sf=args.lsqb_sf, seed=args.seed + 1))
        if args.chips == 4:
            spmd_phase(devs, lsqb)
        else:
            job, dt = timed(lambda: datagen.job_tables(scale=args.job_scale, seed=args.seed))
            emit(phase="datagen", seconds=gen_s + dt)
            analytics_phase(devs, job, lsqb)
            serving_phase(devs, job, args.seed)
            del job
            standing_phase(devs, lsqb, args.seed)
    # the chips this run used, not every chip the host has
    dev = devs[0]
    print(
        json.dumps(
            {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                    "count": len(devs)}}
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
