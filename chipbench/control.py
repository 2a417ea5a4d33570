"""Run a cell with its control in the program's place in the check, to
show that the comparison which decides `correct` fails it.

    python3 chipbench/control.py --workload <cell> --seed <n> --seconds <s> --trace 0

The run is a normal one (set-up, window, metrics). Its program's answers
are checked as in any run, on an earlier output line (`program_correct`);
then the answers compared with the reference are the control's: for a
count, the count over a seeded half of the edges, doubled; for the
standing count, the count from before each acknowledged batch. The
result's `correct` has to read false. The benchmark's own runs never run
it.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from chipbench.run import main

    sys.exit(main(control=True))
