#!/usr/bin/env python3
"""One run of a cell that also reads its control and its faults.

    python chipbench/control.py --workload <cell> --seed <n> --seconds <s> --trace 0

Runs the cell as ``run.py`` does and adds, under ``control``, the same
numbers the check compares, read with the reference put in the
program's place: in float8 (the precision below the configuration's
bfloat16), and for training cells also on half of each batch.  The
limits in ``workloads/<cell>.json`` were set between the program's
readings over a dozen seeds and these (PERF.md).  The benchmark's own
runs never run this.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402

if __name__ == "__main__":
    run.main(control=True)
