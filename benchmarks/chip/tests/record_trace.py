#!/usr/bin/env python3
"""Record the trace that ``test_chipbench_trace.py`` reduces, on a TPU:

    python3 benchmarks/chip/tests/record_trace.py

Runs the tiny AIDA cell of ``chipbench_testkit`` with tracing on (a
0.9 s window, its middle 0.3 s traced) and keeps the trace as
``tests/data/tiny_aida.xplane.pb.gz``; prints the reduction's numbers the
test pins."""
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import chipbench_testkit as kit  # noqa: E402
from chipbench import cell, tracefile  # noqa: E402

OUT = os.path.join(HERE, "data", "tiny_aida.xplane.pb.gz")


class KeepingProfiler(cell.Profiler):
    def close(self):
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(self.xplane(), "rb") as f, gzip.open(OUT, "wb") as g:
            shutil.copyfileobj(f, g)
        super().close()


def main() -> int:
    cell.Profiler = KeepingProfiler
    with tempfile.TemporaryDirectory() as root:
        lay = kit.make_layout(root)
        out = cell.run_cell(lay, "tiny-aida.gen", 7, 0.9, True,
                            process_start=time.perf_counter(),
                            arch=kit.tiny_arch())
    red = tracefile.reduce(OUT)
    print(json.dumps({"bytes": os.path.getsize(OUT),
                      "window_s": red.window_s, "busy_s": red.busy_s,
                      "spmv_s": red.ops_matching(r"^_spmv_call"),
                      "gaps": red.gaps_s, "metrics": out["metrics"],
                      "correct": out["correct"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
