#!/usr/bin/env python3
"""The control of a cell's correctness check: one run of the cell, as
``run.py`` makes it, in which the reference one precision below the
configuration's (matmul operands in float8 e4m3 for bf16 compute) is put
in the program's place.  At each served position it takes the token the
control puts first, and the cell's own verdict over those gaps is the
result's ``correct``, which must come out false.  The result line gains
``control``: the widest gap of the served tokens and whether they pass
(the program's reading on this seed), and the control's widest gap.

    python3 benchmarks/chip/control.py --workload <cell> --seed <n> \\
        --seconds <window>

The benchmark's own runs never run the control."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(control=True))
