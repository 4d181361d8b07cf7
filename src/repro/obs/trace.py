"""Deterministic tick-clock structured event tracer for the serving stack.

One :class:`Tracer` collects *spans* (work that occupies ticks — decode
steps, chunked-prefill steps) and *instant events* (admissions,
preemptions, handoffs, page allocations, fault injections) from every
seam the stack already has.  Two properties make it useful as a CI
artifact and not just a debugging aid:

* **tick clock, not wall clock** — every event is stamped with the
  scheduler tick it happened on (plus role / slot / rid coordinates).
  For a fixed seed the serving stack's decisions are deterministic, so
  the exported event stream is *byte-identical across replays* and CI
  can diff two same-seed runs (wall-time phase timers live separately,
  see :class:`WallTimers`, and never enter the event stream).
* **zero cost when disabled** — sessions hold :data:`NULL` (a no-op
  tracer with ``enabled = False``) unless the caller passes a live one;
  hot-path seams (allocator, prefix cache, scheduler) are wired only
  when a live tracer is attached, so the off path adds nothing.

The Chrome/Perfetto ``trace_event`` exporter maps roles to processes
and slots to threads: load the exported JSON in https://ui.perfetto.dev
and a serve run renders as a per-role, per-slot timeline (one tick =
:data:`TICK_US` microseconds on the rendered axis).

Beside the tick clock sits the profiler's clock: :class:`span` opens a
``jax.profiler.TraceAnnotation``, so the serving session's host spans
(``session.step`` and its children) share a ``jax.profiler`` trace with
the device's program and op events, and :data:`compile_events` logs every
trace and compile of the process on ``time.perf_counter``.
"""
from __future__ import annotations

import collections
import contextlib
import json
import time
from typing import Deque, Dict, List, Optional, Tuple

import jax
from jax import monitoring
from jax.profiler import TraceAnnotation

#: microseconds one scheduler tick occupies on the exported timeline
#: (purely presentational: ticks are the real clock)
TICK_US = 1000

#: stable process ids for the known roles; unknown roles are assigned
#: deterministically (sorted by name) after these
ROLE_PIDS = {"engine": 1, "prefill": 1, "decode": 2}

#: event names emitted by the serving stack (reference, not enforced —
#: the schema check in benchmarks/validate_trace.py validates shape)
EVENT_NAMES = (
    "req.submit", "req.first_token", "req.finish",
    "sched.admit", "sched.preempt", "sched.block", "sched.shed",
    "step.decode", "step.prefill",
    "handoff.enqueue", "handoff.deliver", "handoff.migrate",
    "handoff.fallback", "handoff.oversized",
    "alloc.pages", "alloc.free", "alloc.holdback",
    "prefix.hit", "prefix.pin", "prefix.release",
    "fault.injected", "resil.fail", "resil.degrade",
    "health.audit",
)


class NullTracer:
    """The disabled tracer: every emit is a no-op, ``enabled`` is False
    so seams that need to build expensive args can skip entirely."""

    enabled = False
    recorder = None

    def instant(self, name, **kw):
        pass

    def span(self, name, **kw):
        pass

    def crash(self, reason, **context):
        pass


#: the shared disabled tracer — sessions default to this
NULL = NullTracer()


class Tracer:
    """Collects structured events on the scheduler tick clock.

    ``capture=False`` keeps no full event list (useful when only the
    flight-recorder ring matters); a ``recorder`` (obs.FlightRecorder)
    receives every event regardless and is dumped by :meth:`crash`.
    """

    enabled = True

    def __init__(self, capture: bool = True, recorder=None):
        self.capture = capture
        self.recorder = recorder
        self.events: List[dict] = []
        self.wall = WallTimers()

    # ------------------------------------------------------------ emit
    def _emit(self, ev: dict) -> None:
        if self.capture:
            self.events.append(ev)
        if self.recorder is not None:
            self.recorder.record(ev)

    def instant(self, name: str, *, tick: int, role: str = "engine",
                slot: Optional[int] = None, **args) -> None:
        """A point event at ``tick`` (admission, handoff, fault, ...)."""
        self._emit({"name": name, "ph": "i", "tick": int(tick),
                    "role": role, "slot": slot, "args": args})

    def span(self, name: str, *, tick: int, dur: int = 1,
             role: str = "engine", slot: Optional[int] = None,
             **args) -> None:
        """Work occupying ``dur`` ticks starting at ``tick`` (a decode
        or prefill step)."""
        self._emit({"name": name, "ph": "X", "tick": int(tick),
                    "dur": int(dur), "role": role, "slot": slot,
                    "args": args})

    def crash(self, reason: str, **context) -> Optional[str]:
        """Flush the flight recorder to disk (HealthError / OutOfPages /
        RequestFailed post-mortems).  Returns the dump path, if any."""
        if self.recorder is None:
            return None
        return self.recorder.dump(reason=reason, context=context)

    # ---------------------------------------------------------- export
    def to_chrome(self) -> dict:
        """Chrome/Perfetto ``trace_event`` JSON: roles become processes,
        slots become threads (tid 0 = role-level events)."""
        pids: Dict[str, int] = dict(ROLE_PIDS)
        for ev in self.events:
            if ev["role"] not in pids:
                pids[ev["role"]] = 0   # placeholder, assigned below
        nxt = max(pids.values(), default=0) + 1
        for role in sorted(r for r, p in pids.items() if p == 0):
            pids[role] = nxt
            nxt += 1
        out: List[dict] = []
        seen_threads = set()
        for role in sorted({ev["role"] for ev in self.events},
                           key=lambda r: (pids[r], r)):
            out.append({"name": "process_name", "ph": "M", "pid": pids[role],
                        "tid": 0, "args": {"name": role}})
        for ev in self.events:
            pid = pids[ev["role"]]
            tid = 0 if ev["slot"] is None else int(ev["slot"]) + 1
            if tid and (pid, tid) not in seen_threads:
                seen_threads.add((pid, tid))
                out.append({"name": "thread_name", "ph": "M", "pid": pid,
                            "tid": tid,
                            "args": {"name": f"slot {tid - 1}"}})
            rec = {"name": ev["name"], "ph": ev["ph"], "pid": pid,
                   "tid": tid, "ts": ev["tick"] * TICK_US,
                   "args": dict(ev["args"], tick=ev["tick"])}
            if ev["ph"] == "X":
                rec["dur"] = ev["dur"] * TICK_US
            elif ev["ph"] == "i":
                rec["s"] = "t"
            out.append(rec)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Perfetto-loadable trace; deterministic serialization
        (sorted keys) so same-seed replays are byte-identical."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1, sort_keys=True)
            f.write("\n")
        return path


class WallTimers:
    """Wall-clock seconds and calls per :class:`span` name (``tracer.wall``
    of a live tracer): a view of the host spans, never a timer of its
    own.  Kept apart from the event stream: wall time is host noise and
    would break replay-identical traces."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds
        self.calls[name] = self.calls.get(name, 0) + 1

    def summary(self) -> dict:
        total = sum(self.seconds.values())
        return {name: {"seconds": round(self.seconds[name], 4),
                       "calls": self.calls[name],
                       "share": round(self.seconds[name] / total, 4)
                       if total > 0 else None}
                for name in sorted(self.seconds)}


class span:
    """A host span on the profiler's clock: ``with obs.span(name, tracer,
    **args) as ann`` opens ``jax.profiler.TraceAnnotation(name, **args)``
    (``ann.set_metadata(...)`` adds arguments while it is open), which
    lands in a ``jax.profiler`` trace beside the device's ``XLA Modules``
    / ``XLA Ops`` events and costs one inactive annotation when no trace
    is running.  With a live ``tracer`` its wall time also adds to
    ``tracer.wall`` under ``name``."""

    __slots__ = ("_name", "_ann", "_wall", "_t0")

    def __init__(self, name: str, tracer=None, **args):
        self._name = name
        self._ann = TraceAnnotation(name, **args)
        self._wall = tracer.wall if tracer is not None and tracer.enabled \
            else None

    def __enter__(self):
        self._ann.__enter__()
        if self._wall is not None:
            self._t0 = time.perf_counter()
        return self._ann

    def __exit__(self, *exc):
        if self._wall is not None:
            self._wall.add(self._name, time.perf_counter() - self._t0)
        return self._ann.__exit__(*exc)


#: ``(time.perf_counter() at its end, "trace:<fn>" | "compile:<fn>",
#: seconds)`` of every jaxpr trace and every backend compile or
#: persistent-cache load in this process, newest last
compile_events: Deque[Tuple[float, str, float]] = \
    collections.deque(maxlen=4096)

_COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                   "/jax/core/compile/backend_compile_duration": "compile"}


def _on_duration(event: str, seconds: float, **kw) -> None:
    kind = _COMPILE_EVENTS.get(event)
    if kind is not None:
        compile_events.append((time.perf_counter(),
                               f"{kind}:{kw.get('fun_name', '?')}",
                               seconds))


monitoring.register_event_duration_secs_listener(_on_duration)


@contextlib.contextmanager
def profile_trace(log_dir: Optional[str]):
    """Optional ``jax.profiler`` trace around the compiled steps: a
    no-op when ``log_dir`` is falsy, so callers can thread the flag
    through unconditionally (serve.py ``--profile-dir``)."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
