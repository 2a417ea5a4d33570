"""Helpers for the chip benchmark's CPU tests: a cell at a size a test run
can hold, its driver set up, and one window driven through the check. They
skip the harness's look for a chip (chipbench/run.py) and call the rest of
a run directly."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import harness  # noqa: E402
from chipbench.trace import Tracer  # noqa: E402

# tiny scales of each configuration's generator, for the CPU
SMALL = {"gap-kron": {"scale": 8}}
# a standing mix that stays in one size bucket at that scale
SMALL_STANDING = {"batch_edges": 64, "window_batches": 8}
SEED = 2**31 + 12345  # the driver's seeds are large
# the closed-loop mix kept for a later cell: its end-to-end metrics
CLOSED_LOOP_METRICS = [
    {"name": "queries_per_s", "unit": "queries/s"},
    {"name": "setup_s", "unit": "s"},
]


def small_cell(name: str, config: str | None = None, **mix) -> harness.Cell:
    """A cell of BENCHMARK.json, or with `config` the mix file `name` on
    that configuration (a mix kept for a later cell), at the small size."""
    if config is None:
        cell = harness.Cell.load(name)
    else:
        cell = harness.Cell(
            name=name,
            workload={"name": name, "config": config, "traffic": name, "chips": 1},
            config=harness.load_config(config),
            mix=harness.load_mix(name),
            end_to_end=CLOSED_LOOP_METRICS,
            per_layer=[],
        )
    cell.config["params"].update(SMALL[cell.workload["config"]])
    if cell.mix["driver"] == "standing":
        cell.mix.update(SMALL_STANDING)
    cell.mix.update(mix)
    return cell


def prepared(name: str, seconds: float = 1.0, seed: int = SEED, config=None, **mix):
    """The cell's driver after set-up, at the small size."""
    import jax

    run = harness.Run(
        cell=small_cell(name, config, **mix),
        seed=seed,
        seconds=seconds,
        trace=False,
        devices=jax.devices()[:1],
        log=lambda rec: None,
    )
    run.tracer = Tracer(False, "")
    driver = harness.load_driver(run.mix["driver"]).Driver(run)
    driver.setup()
    return driver


def finish(driver, control: bool = False) -> harness.Checks:
    """The window, then the check, as a run makes them."""
    driver.window(driver.run.seconds)
    driver.release()
    return driver.check(control=control)
