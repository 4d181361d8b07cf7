"""Reduce a ``jax.profiler`` trace by the program's own instrumentation,
where ``chipbench.tracefile`` goes by Python frames and HLO numbers:

- **idle by session span**: each idle piece of the device in the traced
  window laid at the innermost ``session.*`` span (``Session``'s host
  spans, ``repro.obs.span``) open over it; Python frames and ``bench.*``
  spans are passed over, and idle time under no session span is
  ``(outside the program)``;
- **device time by scope**: each op's self time laid at the
  ``jax.named_scope`` that made it (``kv``, ``attention``, ``proj``,
  ``logits``, by the scope's first dotted component), found through its
  enclosing ``XLA Modules`` event's program name and that program's
  instruction metadata (``Session.op_scopes()``); ``(no scope)`` for the
  rest, ops of programs outside ``op_scopes`` among them.

:func:`readings` turns these and the session's step records
(``Session.step_records``) into per-layer readings.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import tracefile

SPAN_PREFIX = "session."
OUTSIDE = "(outside the program)"
NO_SCOPE = "(no scope)"
#: first dotted components of the program's scopes
SCOPES = ("kv", "attention", "proj", "logits")
#: the session spans each idle share reads; ``session.step`` holds the
#: step's own bookkeeping between its children
IDLE_SHARES = {
    "sampler.idle_share": ("session.readback", "session.sample"),
    "kv.alloc_idle_share": ("session.pages",),
    "sched.idle_share": ("session.step", "session.admit", "session.feed",
                         "session.dispatch"),
}

Event = Tuple[int, int, str]


@dataclasses.dataclass
class ProgramReduction:
    window_s: float
    busy_s: float                       # device busy, averaged over chips
    program_idle_s: Dict[str, float]    # idle by innermost session span
    scope_s: Dict[str, float]           # op self time by scope
    unscoped_s: Dict[str, float]        # (no scope) op self time by op

    def unscoped_top(self, n: int = 10) -> List[List]:
        top = sorted(self.unscoped_s.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]


def scope_of(op_name: str) -> str:
    """The innermost program scope in ``op_name`` metadata
    (``jit(f)/while/body/attention/kv.read/gather`` -> ``kv``), by its
    first dotted component; the last path entry is the op itself."""
    for part in reversed(op_name.split("/")[:-1]):
        head = part.split(".")[0]
        if head in SCOPES:
            return head
    return NO_SCOPE


def self_times(events: Sequence[Event]) -> List[int]:
    """Each event's time less that of the events nested in it, in the
    order given (``tracefile.self_times`` does this by name)."""
    own = [b - a for a, b, _ in events]
    stack: List[Tuple[int, int]] = []          # (end, index) of open events
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    for i in order:
        a, b, _ = events[i]
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= b - a
        stack.append((b, i))
    return own


def program_idle(devices, spans: Iterable[Event], w0: int,
                 w1: int) -> Dict[str, int]:
    """Idle ns of the window summed over chips by innermost session
    span; ``devices`` as for :func:`reduce_events`."""
    spans = [s for s in spans if s[2].startswith(SPAN_PREFIX)
             and s[0] < w1 and s[1] > w0]
    out: Dict[str, int] = {}
    for mods, _ in devices:
        busy = tracefile.union(tracefile.clip([m[:2] for m in mods], w0, w1))
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            for label, t in tracefile.label_gap(spans, a, b):
                label = label if label.startswith(SPAN_PREFIX) else OUTSIDE
                out[label] = out.get(label, 0) + t
    return out


def scope_time(devices, op_scopes: Dict[str, Dict[str, str]], w0: int,
               w1: int) -> Tuple[Dict[str, int], Dict[str, int]]:
    """(op self ns by scope, (no scope) op self ns by op) over the
    window, summed over chips."""
    by_scope: Dict[str, int] = {}
    unscoped: Dict[str, int] = {}
    for mods, ops in devices:
        mods = sorted(m for m in mods if m[1] > w0 and m[0] < w1)
        ops = [(max(a, w0), min(b, w1), n) for a, b, n in ops
               if b > w0 and a < w1]
        ops.sort(key=lambda e: (e[0], -e[1]))
        j = 0
        for (a, _, text), t in zip(ops, self_times(ops)):
            while j < len(mods) and mods[j][1] <= a:
                j += 1
            module = mods[j][2].split("(")[0] \
                if j < len(mods) and mods[j][0] <= a else ""
            instr = text.partition(" = ")[0].lstrip("%")
            op = op_scopes.get(module, {}).get(instr)
            scope = scope_of(op) if op is not None else NO_SCOPE
            by_scope[scope] = by_scope.get(scope, 0) + t
            if scope == NO_SCOPE:
                key = f"{module} {tracefile.op_name(text)}".strip()
                unscoped[key] = unscoped.get(key, 0) + t
    return by_scope, unscoped


def reduce_events(devices, host_lines,
                  op_scopes: Dict[str, Dict[str, str]]) -> ProgramReduction:
    """``devices``: [(module events, op events)] per chip, each event
    ``(start_ns, end_ns, name)``; ``host_lines``: every host thread's
    events, the one holding ``bench.window`` also holding the session's
    spans."""
    py = next((ln for ln in host_lines
               if any(n == tracefile.WINDOW_SPAN for _, _, n in ln)), None)
    if py is None:
        raise ValueError(f"no {tracefile.WINDOW_SPAN!r} span in the trace")
    w0, w1 = next((a, b) for a, b, n in py if n == tracefile.WINDOW_SPAN)
    n = max(1, len(devices))
    busy = sum(b - a for mods, _ in devices for a, b in tracefile.union(
        tracefile.clip([m[:2] for m in mods], w0, w1)))
    idle = program_idle(devices, py, w0, w1)
    scope, unscoped = scope_time(devices, op_scopes, w0, w1)
    per_chip = lambda d: {k: v / n / 1e9 for k, v in d.items()}
    return ProgramReduction(window_s=(w1 - w0) / 1e9, busy_s=busy / n / 1e9,
                            program_idle_s=per_chip(idle),
                            scope_s=per_chip(scope),
                            unscoped_s=per_chip(unscoped))


def reduce(path: str, op_scopes) -> ProgramReduction:
    """Reduce the trace at ``path`` (``.xplane.pb``, or gzipped)."""
    import gzip
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices, host_lines = [], []
    for plane in data.planes:
        lines = {ln.name: [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in ln.events] for ln in plane.lines}
        if tracefile.DEVICE_PLANE.match(plane.name):
            devices.append((lines.get("XLA Modules", []),
                            lines.get("XLA Ops", [])))
        elif plane.name.startswith("/host:"):
            host_lines.extend(lines.values())
    return reduce_events(devices, host_lines, op_scopes)


def readings(red: Optional[ProgramReduction], records: Sequence[dict],
             steps: Sequence, window: Tuple[float, float]) -> Dict:
    """The per-layer readings of the program's spans, scopes and step
    records: idle shares of the traced window (%), the ``kv`` share of
    device busy time (%), and logits bytes copied to the host per sampled
    token over the steps inside ``window`` (``steps``: the loop's step
    logs, joined to ``records`` on the step index)."""
    out: Dict[str, float] = {}
    if red is not None and red.window_s > 0:
        for name, spans in IDLE_SHARES.items():
            out[name] = 100.0 * sum(red.program_idle_s.get(s, 0.0)
                                    for s in spans) / red.window_s
        if red.busy_s > 0:
            out["step.kv_share"] = 100.0 * red.scope_s.get("kv", 0.0) \
                / red.busy_s
    w0, w1 = window
    inside = {s.index for s in steps if s.start >= w0 and s.end <= w1}
    recs = [r for r in records if r["step"] in inside]
    sampled = sum(r["sampled"] for r in recs)
    if sampled:
        out["sampler.d2h_bytes_per_token"] = \
            sum(r["d2h_bytes"] for r in recs) / sampled
    return out
