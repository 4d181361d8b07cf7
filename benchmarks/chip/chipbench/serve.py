"""The serving loop: offers a cell's traffic to a live ``Session`` on the
host clock, steps the session one model call at a time, and logs every
request's due time, admission and tokens.

The loop calls the session's public surface (``submit``,
``run_workload(max_steps=1)``, ``results``, ``failed``, ``records``,
``stats``) with one exception, kept in :func:`hook_emissions`: the
session has no public per-token event, so the loop wraps its private
``_emit`` and reads ``slot_entry`` / ``slot_out`` there.

Every call into the program sits in a ``jax.profiler.TraceAnnotation``
named for what the host is doing (``bench.submit``, ``bench.step``,
``bench.wait``), and the traced part of the window in ``bench.window``,
so that idle gaps of the device can be laid at the door of a host span.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

from chipbench import traffic
from chipbench.traffic import Req

clock = time.perf_counter


@dataclasses.dataclass
class RequestLog:
    req: Req
    due: float
    client: int = -1
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    steps: List[int] = dataclasses.field(default_factory=list)
    admit_time: Optional[float] = None
    admit_step: Optional[int] = None
    finished: bool = False
    failed: bool = False

    @property
    def rid(self) -> int:
        return self.req.index

    @property
    def first_token(self) -> Optional[float]:
        return self.times[0] if self.times else None


@dataclasses.dataclass
class StepLog:
    index: int          # the session's model-call count before this call
    start: float
    end: float


@dataclasses.dataclass
class Served:
    """What one run of the loop observed (host clock, seconds)."""
    logs: Dict[int, RequestLog]
    steps: List[StepLog]
    window: Tuple[float, float]    # measured: tokens, rates, gaps
    due_window: Tuple[float, float]  # requests due in it count for tails
    traced: Optional[Tuple[float, float]]
    stats: Dict
    give_up: float                 # end of the wait for late first tokens


def hook_emissions(sess, sink: List[Tuple[int, int]]) -> None:
    """Record (rid, token) of every token the session emits.  The one
    reach into private session state (see the module docstring)."""
    emit = sess._emit

    def emitted(i, logits_i, now):
        rid = sess.slot_entry[i].req.rid
        emit(i, logits_i, now)
        sink.append((rid, sess.slot_out[i][-1]))
    sess._emit = emitted


class Loop:
    """One run of a mix against a session.

    Open loop (``loop: open``): requests arrive at their due times
    whatever the system does; the window opens ``ramp_s`` after the
    first arrival, and after it closes the loop goes on offering load
    until every request due in the window has its first token, or
    ``drain_s`` has passed.  Closed loop (``loop: closed``): ``clients``
    clients each send their next request when the last completes; the
    window opens once every client's first request has a token."""

    def __init__(self, sess, mix: Dict, requests, *, make_request,
                 annotate: Callable, profiler=None):
        self.sess = sess
        self.mix = mix
        self.requests = requests          # iterator of traffic.Req
        self.make_request = make_request  # traffic.Req -> program Request
        self.annotate = annotate          # name -> context manager
        self.profiler = profiler          # (start(), stop()) or None
        self.sink: List[Tuple[int, int]] = []
        hook_emissions(sess, self.sink)
        self.logs: Dict[int, RequestLog] = {}
        self.steps: List[StepLog] = []
        # results of requests served before the loop (the warm-up)
        self.done_seen = self.done0 = len(sess.results)
        self.fail_seen = self.fail0 = len(sess.failed)

    # ------------------------------------------------------------ helpers
    def _submit(self, req: Req, due: float, client: int = -1) -> None:
        self.logs[req.index] = RequestLog(req, due, client)
        self.sess.submit(self.make_request(req))

    def _in_flight(self) -> int:
        return (len(self.logs) - len(self.sess.results) + self.done0
                - len(self.sess.failed) + self.fail0)

    def _step(self) -> List[int]:
        """One model call; returns the rids that completed in it."""
        before = self.sess.stats["steps"]
        t0 = clock()
        with self.annotate("bench.step"):
            self.sess.run_workload([], max_steps=1, on_incomplete="ignore")
        t1 = clock()
        if self.sess.stats["steps"] > before:
            self.steps.append(StepLog(before, t0, t1))
        for rid, tok in self.sink:
            log = self.logs[rid]
            log.times.append(t1)
            log.tokens.append(tok)
            log.steps.append(before)
        self.sink.clear()
        done = [r.rid for r in self.sess.results[self.done_seen:]]
        self.done_seen = len(self.sess.results)
        for rid in done:
            self.logs[rid].finished = True
        for f in self.sess.failed[self.fail_seen:]:
            self.logs[f.rid].failed = True
            done.append(f.rid)
        self.fail_seen = len(self.sess.failed)
        return done

    # --------------------------------------------------------------- runs
    def run(self, seconds: float, trace_at: Optional[float] = None,
            trace_s: float = 0.0) -> Served:
        """Offer the mix until the window of ``seconds`` has closed (and,
        open loop, the wait for its late first tokens is over); trace
        ``trace_s`` seconds from ``trace_at`` into the window."""
        closed = self.mix["loop"] == "closed"
        start = clock()
        win = [None, None]
        due_window = None
        traced = [None, None]
        give_up = None
        if closed:
            with self.annotate("bench.submit"):
                for c in range(int(self.mix["clients"])):
                    self._submit(next(self.requests), start, c)
            firsts = list(self.logs.values())
        else:
            opens = start + int(self.mix["ramp_blocks"]) \
                * traffic.block_seconds(self.mix)
            due_window = (opens, opens + seconds)
            nxt = next(self.requests)
        while True:
            if not closed and start + nxt.due_s <= clock():
                with self.annotate("bench.submit"):
                    while start + nxt.due_s <= clock():
                        self._submit(nxt, start + nxt.due_s)
                        nxt = next(self.requests)
            if self._in_flight():
                done = self._step()
                if closed and done:
                    t = clock()
                    with self.annotate("bench.submit"):
                        for rid in done:
                            self._submit(next(self.requests), t,
                                         self.logs[rid].client)
            else:
                with self.annotate("bench.wait"):
                    time.sleep(max(0.0, start + nxt.due_s - clock()))
            t = clock()
            if win[0] is None:
                if closed and all(r.times for r in firsts):
                    win[0] = t
                    due_window = (t, t + seconds)
                elif not closed and t >= due_window[0]:
                    win[0] = due_window[0]
                continue
            if trace_at is not None and traced[0] is None \
                    and t >= win[0] + trace_at:
                self.profiler.start()
                traced[0] = clock()
            elif traced[0] is not None and traced[1] is None \
                    and (t >= traced[0] + trace_s or t >= win[0] + seconds):
                traced[1] = clock()
                self.profiler.stop()
            if win[1] is None:
                if t < win[0] + seconds:
                    continue
                win[1] = t
                give_up = t + float(self.mix.get("drain_s", 0.0))
            if closed or t >= give_up or self._all_due_served(due_window):
                break
        if traced[0] is not None and traced[1] is None:
            traced[1] = clock()
            self.profiler.stop()
        self._read_records()
        return Served(self.logs, self.steps, tuple(win), due_window,
                      None if traced[0] is None else tuple(traced),
                      dict(self.sess.stats), give_up)

    def _all_due_served(self, window) -> bool:
        return all(log.times or log.failed for log in self.logs.values()
                   if window[0] <= log.due < window[1])

    def _read_records(self) -> None:
        for rec in self.sess.records:
            log = self.logs.get(rec["rid"])
            if log is not None:
                log.admit_time = rec.get("admit_time")
                log.admit_step = rec.get("admit_step")
