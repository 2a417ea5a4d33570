"""The chip benchmark's harness, driven by data.

A cell of BENCHMARK.json names a configuration and a traffic mix. The
harness finds each unit by its name:

* `chipbench/configs/<config>.json`: the deployment (generator, sizes,
  guarantees);
* `chipbench/mixes/<traffic>.json`: the traffic's parameters; its
  `driver` key names the general loop that reads them;
* `chipbench/drivers/<driver>.py`: set-up, the measured window and the
  check of every answer against the plain reference (`reference.py`);
* `chipbench/metrics/<metric>.py`: one reader per per-layer metric.

A later cell, mix or metric is new files plus BENCHMARK.json entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRACE_DIR = ROOT / ".chipbench" / "trace"


class BenchError(RuntimeError):
    """The benchmark cannot run as asked: an unknown name, a device that is
    not in the peaks table, a missing accelerator."""


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(kind: str, name: str, bench: dict) -> dict:
    for entry in bench[kind]:
        if entry["name"] == name:
            return entry
    raise BenchError(f"no {kind[:-1]} named {name!r} in BENCHMARK.json")


def _json_file(path: Path, what: str) -> dict:
    if not path.is_file():
        raise BenchError(f"{what}: no file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def _module(path: Path, what: str):
    if not path.is_file():
        raise BenchError(f"{what}: no file {path.relative_to(ROOT)}")
    name = "chipbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str) -> dict:
    return _json_file(BENCH_DIR / "configs" / f"{name}.json", f"configuration {name!r}")


def load_mix(name: str) -> dict:
    return _json_file(BENCH_DIR / "mixes" / f"{name}.json", f"traffic mix {name!r}")


def load_driver(name: str):
    return _module(BENCH_DIR / "drivers" / f"{name}.py", f"driver {name!r}")


def load_metric(name: str):
    return _module(BENCH_DIR / "metrics" / f"{name}.py", f"metric {name!r}")


def peaks(device_kind: str) -> dict:
    table = _json_file(BENCH_DIR / "peaks.json", "peaks table")
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in chipbench/peaks.json")
    return table["devices"][device_kind]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    """Everything one run of one cell reads: the BENCHMARK.json entries
    and the files they name."""

    name: str
    workload: dict
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, name: str, bench: dict | None = None) -> "Cell":
        bench = bench or load_benchmark()
        w = _named("workloads", name, bench)
        return cls(
            name=name,
            workload=w,
            config=load_config(w["config"]),
            mix=load_mix(w["traffic"]),
            end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
            per_layer=[m for m in bench["per_layer"] if applies(m, name)],
        )


# ---------------------------------------------------------------------------
# what a run carries


class Spans:
    """The benchmark's own host spans around calls into each layer: kept
    in memory with their host-clock durations, and written into the
    profiler's trace as TraceAnnotations while it runs."""

    def __init__(self):
        self.names: set[str] = set()
        self.totals: dict[str, list] = {}  # name -> [count, seconds] while on

    def __call__(self, name: str):
        return _Span(self, name)

    def is_bench_span(self, name: str) -> bool:
        return name in self.names


class _Span:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name
        spans.names.add(name)

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        tot = self.spans.totals.setdefault(self.name, [0, 0.0])
        tot[0] += 1
        tot[1] += dt
        return False


class CompileCounter:
    """Counts the executables the process builds, through jax.monitoring:
    `count` every one, `loads` those read back from the persistent compile
    cache (the rest were compiled). Registered once per process."""

    def __init__(self):
        self.count = 0
        self.loads = 0
        self._on = False

    def install(self) -> "CompileCounter":
        if not self._on:
            import jax
            from jax._src import dispatch

            def on_duration(event, _secs, **_kw):
                if event == dispatch.BACKEND_COMPILE_EVENT:
                    self.count += 1

            def on_event(event, **_kw):
                if event == "/jax/compilation_cache/cache_hits":
                    self.loads += 1

            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
            self._on = True
        return self

    def compiled(self) -> int:
        return self.count - self.loads


COMPILES = CompileCounter()


@dataclass
class Run:
    """One run of a cell, as its driver and metric readers see it."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    spans: Spans = field(default_factory=Spans)
    tracer: object = None
    log: object = None  # callable(dict): an earlier output line

    @property
    def mix(self) -> dict:
        return self.cell.mix

    @property
    def config(self) -> dict:
        return self.cell.config


def generate(config: dict, seed: int):
    """The configuration's tables and queries, by the generator named in
    its file. The generator draws its rows from the file's fixed
    `data_seed`; `seed` gives each table's rows a random order. Every seed
    thus gets the same tables and the same base work: rows drawn from the
    seed moved a JOB pass by 20% from seed to seed, and relabelled ids
    moved JOB's by 10% and LSQB's cyclic counts by 17% (hash layouts)."""
    import numpy as np

    from chipbench import datagen
    from repro.relational.relation import Relation

    tables = getattr(datagen, config["generator"])(**config["params"], seed=config["data_seed"])
    rng = np.random.default_rng([seed, 0x1D5])
    out = {}
    for name, t in tables.items():
        order = rng.permutation(t.num_rows)
        out[name] = Relation(t.name, {c: v[order] for c, v in t.columns.items()})
    queries = getattr(datagen, config["queries"])(out)
    return out, {name: (q, rels) for name, q, rels in queries}


def plain(query, rels) -> tuple[list, dict]:
    """A query and its relations in the reference's plain terms:
    [(alias, vars)] and {alias: {var: numpy column}} (the host columns)."""
    import numpy as np

    atoms = [(a.alias, tuple(a.vars)) for a in query.atoms]
    data = {a: {v: np.asarray(rels[a].columns[v]) for v in vs} for a, vs in atoms}
    return atoms, data


@dataclass
class Checks:
    """What was compared, each number with its limit."""

    attempted: int = 0
    wrong: int = 0
    degraded: int = 0
    missing: int = 0

    def numbers(self) -> dict:
        return {
            "wrong_answers": {"value": self.wrong, "limit": 0},
            "degraded_answers": {"value": self.degraded, "limit": 0},
            "missing_answers": {"value": self.missing, "limit": 0},
        }

    @property
    def failed(self) -> int:
        return self.wrong + self.degraded + self.missing

    @property
    def correct(self) -> bool:
        return self.attempted > 0 and all(
            v["value"] <= v["limit"] for v in self.numbers().values()
        )


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def execute(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    devices,
    *,
    t_start: float,
    log=None,
    control: bool = False,
) -> dict:
    """One run: set-up, the window, the check, the metrics. Returns the
    result object. `control` puts the reference's control in the program's
    place in the check (see each driver's `check`)."""
    from chipbench.trace import Tracer

    COMPILES.install()
    run = Run(
        cell=cell,
        seed=seed,
        seconds=seconds,
        trace=trace,
        devices=devices,
        log=log or (lambda rec: None),
    )
    run.tracer = Tracer(trace, str(TRACE_DIR / cell.name))
    driver = load_driver(cell.mix["driver"]).Driver(run)
    driver.setup()
    compiled, loads = COMPILES.compiled(), COMPILES.loads
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    driver.window(seconds)
    run.tracer.stop()
    run.log(
        {
            "phase": "window",
            "setup_s": setup_s,
            "window_s": time.perf_counter() - t_window,
            "compiles_in_window": COMPILES.compiled() - compiled,
            "cache_loads_in_window": COMPILES.loads - loads,
            **driver.window_report(),
        }
    )
    mem = memory_peak_bytes(devices)
    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": mem,
    }
    metrics: dict = {}
    breakdown = None
    if trace:
        metrics, extra, breakdown = _per_layer(run, driver)
        device.update(extra)
    else:
        values = dict(driver.end_to_end())
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if values.get(m["name"]) is None:
                raise BenchError(f"cell {cell.name} produced no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    driver.release()
    t_check = time.perf_counter()
    checks = driver.check()
    run.log({"phase": "check", "seconds": time.perf_counter() - t_check})
    if control:
        # the program's own answers were checked above; the result is the
        # control's, in their place
        run.log({"phase": "check", "program_correct": checks.correct, **checks.numbers()})
        checks = driver.check(control=True)
    result = {
        "correct": checks.correct,
        "attempted": checks.attempted,
        "failed": checks.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks.numbers()
    return result


def _per_layer(run: Run, driver):
    from chipbench import trace as tr

    path = run.tracer.path()
    if path is None:
        raise BenchError("the traced run left no trace file")
    events = tr.load_xplane(path, lambda n: n == tr.SLICE_SPAN or run.spans.is_bench_span(n))
    bounds = tr.slice_bounds(events)
    if bounds is None:
        raise BenchError("the trace holds no slice span")
    red = tr.reduce(events, bounds)
    ctx = MetricContext(
        reduction=red,
        slice=driver.slice_report(),
        peaks=peaks(run.devices[0].device_kind),
    )
    metrics = {}
    for m in run.cell.per_layer:
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    extra = {"busy_s": red.busy_ns / 1e9, "window_s": red.window_ns / 1e9}
    breakdown = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
    return metrics, extra, breakdown


@dataclass
class MetricContext:
    """What a per-layer metric reader reads: the reduced trace of the
    slice, the driver's counts and spans over that slice, the chip's peaks."""

    reduction: object
    slice: dict
    peaks: dict


def tpu_devices(chips: int):
    """The chips the cell asks for; refuses any other backend and too few
    chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(
            f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}; "
            "the benchmark does not run on another backend"
        )
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips and JAX sees {len(devs)}")
    return devs[:chips]


def emit(rec: dict, stream=None) -> None:
    print(json.dumps(rec), file=stream or sys.stdout, flush=True)


def enable_cache() -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    given to the program's own rule (repro.compile_cache) through its
    variable, so a cache set up elsewhere on the machine is never shared.
    Every program goes in, so only a cell's first run in a checkout
    compiles."""
    import jax

    from repro.compile_cache import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax-cache")
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
