"""Run one cell of the chip benchmark on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Earlier output lines are JSON records of set-up and the window; the last
line is the result object. The numbers compared with the reference, each
with its limit, are the last lines on standard error and the `checks` key
of the result. Without a TPU, or with fewer chips than the cell asks for,
it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, control: bool = False) -> int:
    """One run; `control` puts the reference's control in the program's
    place in the check (chipbench/control.py, never the benchmark's runs)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import harness

    try:
        cell = harness.Cell.load(args.workload)
        devices = harness.tpu_devices(cell.workload["chips"])
        harness.enable_cache()
        result = harness.execute(
            cell,
            args.seed,
            args.seconds,
            bool(args.trace),
            devices,
            t_start=T_START,
            log=harness.emit,
            control=control,
        )
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
