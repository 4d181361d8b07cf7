#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <window> --trace <0|1>

Builds the cell's configuration from the seed on the chip, warms up the
shapes the window uses, drives the cell's traffic for ``--seconds``,
checks what was served against the plain reference, and prints one JSON
line last on standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics; with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared beside its limit, which also end
standard error.  Without a TPU, with fewer chips than the cell needs, on
a chip kind without published peaks, or away from the program's sources
it exits non-zero and prints no result.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _finite(x):
    """JSON has no infinity: a non-finite reading is written as null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None, control: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import cell, device
    from chipbench.layout import Layout, LayoutError
    try:
        out = cell.run_cell(Layout(), args.workload, args.seed,
                            args.seconds, bool(args.trace),
                            process_start=PROCESS_START, control=control)
    except device.DeviceError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    except (LayoutError, ImportError, OSError) as e:
        print(f"run.py: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    print(json.dumps(_finite(out)), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
