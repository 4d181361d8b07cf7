#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, traced, and print what the
program's own spans, scopes and counters say about it:

    python3 benchmarks/chip/program_readings.py --workload <cell> \\
        --seed <n> --seconds <window>

The run is ``run.py --trace 1``'s; this script keeps the trace and the
session's step records and compiled-program metadata long enough to
reduce them with ``chipbench.programtrace``.  It prints one JSON line:
``result`` (``run.py``'s result line), ``end_to_end`` (the end-to-end
metrics over the whole window, and ``output_tok_s`` over the traced part
alone), ``readings`` (the per-layer readings of ``programtrace.readings``
and ``engine.compiles_in_window``), the idle split by session span, the
device time by scope and the largest ops with no scope.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def read_cell(layout, name: str, seed: int, seconds: float,
              **run_kw) -> dict:
    """One traced run of cell ``name``; returns the printed line."""
    from chipbench import cell, programtrace
    got = {}

    class KeptProfiler(cell.Profiler):
        """Reduces the trace by the session's spans and scopes before
        the harness deletes it (the session is still open then)."""

        def close(self):
            sess = got.pop("session")
            got["records"] = list(sess.step_records)
            got["program"] = programtrace.reduce(self.xplane(),
                                                 sess.op_scopes())
            super().close()

    class KeptRunView(cell.RunView):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            got["run"] = self

    kept = cell.Profiler, cell.RunView
    cell.Profiler, cell.RunView = KeptProfiler, KeptRunView
    try:
        out = cell.run_cell(layout, name, seed, seconds, True,
                            on_session=lambda s: got.update(session=s),
                            **run_kw)
    finally:
        cell.Profiler, cell.RunView = kept
    run, red = got["run"], got.get("program")
    served = run.served
    e2e = {m: layout.metric(m).read(run)
           for m in ("output_tok_s", "itl_p95_ms", "setup_s")}
    if served.traced is not None:
        t0, t1 = served.traced
        n = sum(1 for log in served.logs.values() for t in log.times
                if t0 < t <= t1)
        e2e["output_tok_s_traced"] = n / (t1 - t0)
    readings = programtrace.readings(red, got.get("records", []),
                                     served.steps, served.window)
    readings["engine.compiles_in_window"] = layout.metric(
        "engine.compiles_in_window").read(run)
    line = {"result": out, "end_to_end": e2e, "readings": readings}
    if red is not None:
        line.update(window_s=red.window_s, busy_s=red.busy_s,
                    program_idle_s=red.program_idle_s,
                    scope_s=red.scope_s, unscoped_top=red.unscoped_top())
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench.layout import Layout
    line = read_cell(Layout(), args.workload, args.seed, args.seconds,
                     process_start=PROCESS_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
