"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy time, each device operation's own time,
and the device's idle gaps, each laid at the door of what the host's
Python thread was doing then.

Layout of a TPU trace (JAX 0.9, v5e): one plane per chip named
``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per program
run) and a line ``XLA Ops`` (one per HLO instruction, nested: a
``while`` holds the ops of its body); host planes ``/host:CPU`` with one
line per thread, the Python thread holding the ``bench.*`` spans and the
Python tracer's frames.  All events share one clock (ns).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Reduction:
    window_s: float                  # length of the traced window
    busy_s: float                    # device busy, averaged over chips
    op_s: Dict[str, float]           # device self time by op name
    gaps_s: Dict[str, float]         # idle time by host activity
    chips: int

    def ops_matching(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def breakdown(self, n: int = 10) -> Dict[str, List]:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def op_name(text: str) -> str:
    """``%copy.59 = bf16[32,160]{...} copy(...)`` -> ``copy.59
    bf16[32,160]``: the instruction and its result shape, no layout."""
    head, _, rest = text.partition(" = ")
    shape = rest.split(" ", 1)[0] if rest else ""
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{head.lstrip('%')} {shape}".strip()


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def self_times(events: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Per name, the time of its events less that of the events nested
    in them (the ``XLA Ops`` line nests a loop's body inside the loop)."""
    total: Dict[str, int] = {}
    child: Dict[str, int] = {}
    stack: List[Tuple[int, str]] = []          # (end, name) of open events
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            child[parent] = child.get(parent, 0) + (end - start)
        stack.append((end, name))
        total[name] = total.get(name, 0) + (end - start)
    return {n: t - child.get(n, 0) for n, t in total.items()}


def innermost(spans: List[Tuple[int, int, str]], t: int) -> Optional[str]:
    best = None
    for a, b, name in spans:
        if a <= t < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else None


def label_gap(spans, a: int, b: int):
    """Split the idle interval [a, b) where host spans start or end, and
    lay each piece at the innermost span open over it."""
    over = [s for s in spans if s[0] < b and s[1] > a]
    cuts = sorted({a, b} | {x for s in over for x in s[:2] if a < x < b})
    for lo, hi in zip(cuts, cuts[1:]):
        yield innermost(over, (lo + hi) // 2) or "(no host span)", hi - lo


def reduce(path: str) -> Reduction:
    """Reduce the trace at ``path`` (``.xplane.pb``, or gzipped)."""
    import gzip
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    host_lines = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
            devices.append((mods, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append([(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events])
    return reduce_events(devices, host_lines)


def reduce_events(devices, host_lines) -> Reduction:
    """The reduction proper, on plain (start, end[, name]) tuples:
    ``devices`` is [(module intervals, op events)] per chip,
    ``host_lines`` every host thread's events."""
    py = next((ln for ln in host_lines
               if any(n == WINDOW_SPAN for _, _, n in ln)), None)
    if py is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = next((a, b) for a, b, n in py if n == WINDOW_SPAN)
    busy_total = 0
    op_ns: Dict[str, int] = {}
    gap_ns: Dict[str, int] = {}
    inner = [s for s in py if s[2] != WINDOW_SPAN and s[0] < w1
             and s[1] > w0]
    for mods, ops in devices:
        busy = union(clip(mods, w0, w1))
        busy_total += sum(b - a for a, b in busy)
        in_win = [(max(a, w0), min(b, w1), op_name(n)) for a, b, n in ops
                  if b > w0 and a < w1]
        for name, t in self_times(in_win).items():
            op_ns[name] = op_ns.get(name, 0) + t
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            for label, t in label_gap(inner, a, b):
                gap_ns[label] = gap_ns.get(label, 0) + t
    n = max(1, len(devices))
    return Reduction(window_s=(w1 - w0) / 1e9, busy_s=busy_total / n / 1e9,
                     op_s={k: v / n / 1e9 for k, v in op_ns.items()},
                     gaps_s={k: v / n / 1e9 for k, v in gap_ns.items()},
                     chips=len(devices))
