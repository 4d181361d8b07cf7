"""Serving session: continuous batching over a fixed-slot decode batch,
driven by the `repro.sched` scheduler subsystem.

Requests occupy slots, finished slots are refilled from the scheduler's
queue without stopping the batch (continuous batching).  The scheduler
(`repro.sched.Scheduler`) decides admission order (FIFO or
shortest-prompt-first), applies page-pool admission control (a request is
admitted only when its worst-case page need fits), and picks preemption
victims under pool pressure (youngest first, recompute-style resume)
instead of letting `OutOfPages` crash the batch.

Prefill is chunked when the KV cache is paged and the arch supports it
(`scheduler=...` with ``chunk=C``): C prompt tokens per model call via
`sched.prefill`, written straight into pool pages — first-token latency
drops from prompt_len calls to ceil(prompt_len/C).  With ``chunk=1``
(default) prompts feed token-by-token through the decode step.

KV cache resolution: ``kv_cache=None`` resolves through REPRO_KV_CACHE
(default "auto"); "auto" picks the paged pool for every arch with
attention layers and falls back to the dense cache for attention-free
ones (rwkv6).  Paged pages are allocated host-side the step a sequence
crosses a page boundary, freed the moment its request completes, and —
on pure-SWA architectures — reclaimed as soon as they slide fully behind
the attention window.  With ``prefix_cache=True`` full prompt pages are
content-hashed and shared across requests (refcounted), so common
prompt heads are prefilled once.

Sessions are created by `repro.api.Engine.session()` (or directly); the
compiled decode step comes from the engine's backend, so dense and
compressed (Pallas) serving share one code path.

Mesh serving: a ``plan`` (repro.shard.ShardingPlan, built by
``Engine.session(mesh=...)``) makes the same session tensor-parallel —
params are shard-padded and placed per the plan, KV pools shard their
head axis, and the decode/prefill steps compile with explicit
input/output shardings.  All host-side bookkeeping (page allocator,
admission, preemption, prefix cache) is placement-agnostic and runs
unchanged; ``plan=None`` is the exact pre-mesh single-device path.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
import time
import warnings
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import kvstore as kvs
from repro import obs as obs_mod
from repro import resil as rsl
from repro import sched as schd
from repro.api import env
from repro.api.registry import Executor, get_backend
from repro.configs.base import ArchConfig

# env knobs resolved ONCE at import via repro.api.env (traced code must
# not read os.environ); per-session override via the kv_cache= /
# kv_dtype= constructor args.  "auto" resolves per-arch in
# resolve_kv_cache: paged for attention archs (exact bf16 pages by
# default — int8 is the opt-in memory lever).
KV_CACHE_DEFAULT = env.KV_CACHE
KV_DTYPE_DEFAULT = env.KV_DTYPE


def resolve_kv_cache(kv_cache: Optional[str], cfg: ArchConfig) -> str:
    """None -> env default; "auto" -> paged wherever there is attention
    state to page (explicit "full" always available)."""
    kv = KV_CACHE_DEFAULT if kv_cache is None else kv_cache
    if kv == "auto":
        kv = "full" if cfg.family == "rwkv6" else "paged"
    return kv


# Compiled decode steps keyed by (backend, cfg): sessions on the same
# config reuse one jitted step (its trace cache handles dense vs
# compressed param structures), so spinning up a Session is cheap.
# The decode state (argnum 1) is DONATED: every step consumes the state
# it is handed and the caller keeps only the returned one — KV
# pool/cache buffers are updated in place, never silently copied.
# Mesh sessions compile per session instead (their in/out shardings
# depend on the session's concrete param/state trees).
_STEP_CACHE: dict = {}

#: model calls :attr:`Session.step_records` keeps, newest last
STEP_RECORDS = 8192

#: one instruction of compiled HLO text and its ``op_name`` metadata
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                     re.M)


def _jitted_step(backend: Executor, cfg: ArchConfig):
    key = (backend.name, cfg)
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = jax.jit(backend.make_decode_step(cfg),
                                   donate_argnums=(1,))
    return _STEP_CACHE[key]


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new: int = 16
    temperature: float = 0.0
    rid: int = 0
    # per-request completion budget in ticks from submit (overrides
    # ResilConfig.deadline_ticks; None = use the session default)
    deadline_ticks: Optional[int] = None


@dataclasses.dataclass
class Result:
    rid: int
    tokens: List[int]


def _unserved_record(req: "Request") -> dict:
    """Lifecycle record for a request that never reached submit() —
    same schema as Session.submit's records, terminal state 'unserved'."""
    return {"rid": req.rid, "prompt_len": len(req.prompt),
            "max_new": req.max_new, "submit_step": None,
            "submit_time": None, "admit_step": None, "admit_time": None,
            "first_token_step": None, "first_token_time": None,
            "finish_time": None, "n_generated": 0, "preemptions": 0,
            "prefix_pages": 0, "state": "unserved",
            "failed_reason": None, "retries": 0}


class Session:
    def __init__(self, cfg: ArchConfig, params, batch_slots: int = 4,
                 max_len: int = 256, seed: int = 0,
                 backend: Optional[Executor] = None,
                 kv_cache: Optional[str] = None, page_size: int = 16,
                 kv_pool_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 scheduler=None, plan=None, resil=None, obs=None):
        assert cfg.has_decode, "encoder archs don't serve autoregressively"
        from repro.models import model as M
        self.cfg, self.params = cfg, params
        self.plan = plan
        self._param_sh = None
        if plan is not None:
            # shard-aware stacking: compressed leaves are padded to the
            # tp degree and placed per the plan; raw leaves get their
            # Megatron TP shardings (replicated over data for serving)
            from repro import shard as shardmod
            self.params, self._param_sh = shardmod.prepare_params(
                plan, cfg, params)
        self.slots = batch_slots
        self.max_len = max_len
        kv_cache = resolve_kv_cache(kv_cache, cfg)
        if cfg.family == "rwkv6":
            kv_cache = "full"      # attention-free: nothing to page
        self.kv_cache = kv_cache
        self.page_size = page_size
        self.kv_dtype = kv_dtype or KV_DTYPE_DEFAULT
        self.sched = schd.Scheduler(schd.SchedConfig.coerce(scheduler))
        # chunked prefill needs pages to write into and attention-only
        # token mixing; elsewhere prompts feed token-by-token
        self.chunk = self.sched.cfg.chunk if (
            kv_cache == "paged"
            and schd.supports_chunked_prefill(cfg)) else 1
        if kv_cache == "paged":
            self.state = M.init_decode_state(
                cfg, batch_slots, max_len, kv_cache="paged",
                page_size=page_size, kv_pool_pages=kv_pool_pages,
                kv_dtype=self.kv_dtype)
            n_pages = jax.tree.leaves(
                self.state["layers"]["kv"])[0].shape[1]
            self.alloc = kvs.PageAllocator(n_pages)
            # host mirror of the device page table (allocation decisions
            # never read device memory back)
            self.host_table = np.full(
                (batch_slots, self.state["page_table"].shape[1]), -1,
                np.int64)
            self.slot_pos = [0] * batch_slots
            wins = cfg.layer_windows()
            # page reclamation is safe only when EVERY layer is windowed
            # (one global layer pins the whole history, like the dense
            # path's ring-vs-full split)
            self._swa_window = max(wins) if wins and all(
                w > 0 for w in wins) else None
            self.prefix = schd.PrefixCache() \
                if self.sched.cfg.prefix_cache else None
        else:
            self.state = M.init_decode_state(cfg, batch_slots, max_len)
            self.alloc = None
            self.prefix = None
        self.key = jax.random.PRNGKey(seed)
        if backend is None or isinstance(backend, str):
            backend = get_backend(backend or "jax-dense")
        self.backend = backend
        if plan is not None:
            # mesh session: KV heads shard over the model axis, page
            # table/pos replicate, and the step compiles with explicit
            # input/output shardings so the donated state buffers keep
            # their placement (no silent gathers/copies per step)
            self._state_sh = plan.state_shardings(self.state)
            self.state = jax.device_put(self.state, self._state_sh)
            rep = plan.replicated()
            step = backend.make_decode_step(cfg, plan=plan)
            self._step = jax.jit(
                step,
                in_shardings=(self._param_sh, self._state_sh, rep),
                out_shardings=(self._state_sh, rep),
                donate_argnums=(1,))
            self._prefill = schd.make_prefill_step(
                cfg, self.chunk, plan=plan,
                in_shardings=(self._param_sh, self._state_sh, rep, rep),
                out_shardings=(self._state_sh, rep)) \
                if self.chunk > 1 else None
        else:
            self._state_sh = None
            self._step = _jitted_step(backend, cfg)
            self._prefill = schd.make_prefill_step(cfg, self.chunk) \
                if self.chunk > 1 else None
        # per-slot bookkeeping (host side)
        self.slot_entry: List[Optional[schd.SchedEntry]] = \
            [None] * batch_slots
        self.slot_pending: List[List[int]] = [[] for _ in range(batch_slots)]
        self.slot_out: List[List[int]] = [[] for _ in range(batch_slots)]
        self.slot_cache_j: List[int] = [0] * batch_slots
        self.results: List[Result] = []
        self.failed: List[rsl.RequestFailed] = []
        self.records: List[dict] = []
        # resilience layer: None (default) is the exact pre-resil path;
        # a ResilState may be shared across roles (disagg) so counters
        # aggregate in one place
        if resil is None or isinstance(resil, rsl.ResilState):
            self.resil = resil
        else:
            self.resil = rsl.ResilState(rsl.ResilConfig.coerce(resil))
        self.role = "engine"       # disagg roles override ("prefill"/...)
        self.tick = 0              # scheduling-opportunity clock
        self.stats = {"steps": 0, "fills": 0, "preemptions": 0,
                      "chunk": self.chunk}
        # one record per model call (see _advance); _emitted and _h2d
        # gather the open call's (rid, token) pairs and host->device bytes
        self.step_records: Deque[dict] = collections.deque(
            maxlen=STEP_RECORDS)
        self._emitted: List[Tuple[int, int]] = []
        self._h2d = 0
        self._step_ann = None      # the open session.step annotation
        if kv_cache == "paged":
            self.stats.update({"page_allocs": 0, "pages_in_use": 0,
                               "pages_peak": 0, "pages_reclaimed_swa": 0,
                               "prefix_hits": 0, "prefix_pages_reused": 0})
        # observability: obs.NULL keeps every seam on the exact pre-obs
        # path (hooks stay None, emits are no-ops); a live obs.Tracer
        # wires the allocator / prefix / scheduler / resil seams so the
        # tick-clock event stream covers the whole request lifecycle
        self.tracer = obs if obs is not None else obs_mod.NULL
        if self.tracer.enabled:
            self._wire_obs()

    def _wire_obs(self) -> None:
        """Attach this session's tracer to the host-side seams.  The
        hook reads ``self.role`` / ``self.tick`` at emit time, so disagg
        roles renamed after construction stamp correctly."""
        def hook(name, **args):
            self.tracer.instant(name, tick=self.tick, role=self.role,
                                **args)
        if self.alloc is not None:
            self.alloc.obs = hook
        if self.prefix is not None:
            self.prefix.obs = hook
        self.sched.obs = hook
        if self.resil is not None:
            if self.resil.degrade is not None:
                self.resil.degrade.obs = hook
            if self.resil.watchdog is not None:
                self.resil.watchdog.obs = hook

    @contextlib.contextmanager
    def _call_span(self):
        """The ``session.step`` span around one scheduling opportunity:
        slot filling, then the model call if any slot is active (which
        sets its ``kind``)."""
        with obs_mod.span("session.step", self.tracer,
                          step=self.stats["steps"]) as ann:
            self._step_ann = ann
            try:
                yield
            finally:
                self._step_ann = None

    def op_scopes(self) -> Dict[str, Dict[str, str]]:
        """``{XLA module: {HLO instruction: op_name metadata}}`` of this
        session's compiled step programs (``jit_serve_decode_step``,
        ``jit_serve_chunked_step``), for laying the device ops of a
        ``jax.profiler`` trace at the ``jax.named_scope`` that made
        them (``kv.write``, ``kv.read``, ``attention``, ``proj``,
        ``logits``).  Lowers and compiles the steps once more on each
        call (the compile cache answers where it is on)."""
        b = self.slots
        calls = [(self._step, (self.params, self.state,
                               jnp.zeros((b,), jnp.int32)))]
        if self._prefill is not None:
            calls.append((self._prefill, (
                self.params, self.state, jnp.zeros((b, self.chunk),
                                                   jnp.int32),
                jnp.zeros((b,), jnp.int32))))
        out = {}
        for fn, args in calls:
            text = fn.lower(*args).compile().as_text()
            module = re.search(r"HloModule (\S+?),", text).group(1)
            out[module] = dict(_HLO_OP.findall(text))
        return out

    # ------------------------------------------------------------ public
    def submit(self, req: Request) -> None:
        entry = self.sched.submit(req, step=self.stats["steps"],
                                  now=time.perf_counter())
        if self.kv_cache == "paged":
            entry.hashes = schd.page_hashes(req.prompt, self.page_size)
        rec = {"rid": req.rid, "prompt_len": len(req.prompt),
               "max_new": req.max_new, "submit_step": entry.submit_step,
               "submit_time": entry.submit_time, "admit_step": None,
               "admit_time": None, "first_token_step": None,
               "first_token_time": None, "finish_time": None,
               "n_generated": 0, "preemptions": 0, "prefix_pages": 0,
               "state": "queued", "failed_reason": None, "retries": 0}
        entry.record = rec
        self.records.append(rec)
        if self.resil is not None:
            entry.deadline_tick = self.resil.deadline_for(req, self.tick)
            rec["deadline_tick"] = entry.deadline_tick
        self.tracer.instant("req.submit", tick=self.tick, role=self.role,
                            rid=req.rid, prompt_len=len(req.prompt),
                            max_new=req.max_new)

    def run(self, max_steps: int = 10_000,
            on_incomplete: str = "raise") -> List[Result]:
        """Drain the queue; returns all results in deterministic rid
        order.  ``on_incomplete``: what to do when ``max_steps`` is
        exhausted (or admission deadlocks) with requests still queued or
        in flight — "raise" (default), "warn" (report partial results),
        or "ignore"."""
        return self.run_workload([], max_steps=max_steps,
                                 on_incomplete=on_incomplete)

    def run_workload(self, arrivals: Sequence[Tuple[int, Request]],
                     max_steps: int = 10_000,
                     on_incomplete: str = "raise") -> List[Result]:
        """Serve timed traffic: ``arrivals`` is [(arrival_step, Request)]
        (see sched.workload); requests already submit()ed count as
        step-0 arrivals.  Idle gaps fast-forward the step clock.

        A ``HealthError`` or ``OutOfPages`` escaping the loop dumps the
        flight recorder (when one is attached) before re-raising, so
        chaos-sweep crashes leave a post-mortem on disk."""
        try:
            return self._run_loop(arrivals, max_steps, on_incomplete)
        except (rsl.HealthError, kvs.OutOfPages) as e:
            self.tracer.crash(type(e).__name__, role=self.role,
                              tick=self.tick, error=str(e))
            raise

    def _run_loop(self, arrivals: Sequence[Tuple[int, Request]],
                  max_steps: int,
                  on_incomplete: str) -> List[Result]:
        pending: Deque[Tuple[int, Request]] = collections.deque(
            sorted(arrivals, key=lambda a: a[0]))
        # the arrival clock mirrors the model-call count but can jump
        # forward over idle gaps; stats["steps"] stays honest (executed
        # model calls only)
        clock = self.stats["steps"]
        for _ in range(max_steps):
            self.tick = clock
            while pending and pending[0][0] <= clock:
                self.submit(pending.popleft()[1])
            if self.resil is not None:
                self._resil_tick(clock)
            with self._call_span():
                self._fill_slots()
                idle = all(e is None for e in self.slot_entry)
                if not idle:
                    self._try_advance()
            if idle:
                if self._fault_waiting():
                    # an injected page spike is holding the pool hostage;
                    # burn the tick so the window can pass instead of
                    # misreading it as an admission deadlock
                    self.resil.count("wait_ticks")
                    clock += 1
                    continue
                if len(self.sched):
                    self._incomplete(on_incomplete, blocked=True,
                                     pending=pending)
                    break
                if pending:        # idle until the next arrival
                    clock = pending[0][0]
                    continue
                break
            clock += 1
        else:
            self._incomplete(on_incomplete, blocked=False, pending=pending)
        return sorted(self.results, key=lambda r: r.rid)

    def _try_advance(self) -> None:
        """One model call of the co-located loop; an injected fault or a
        spike-squeezed pool loses the tick, not the work."""
        try:
            self._advance()
        except rsl.InjectedFault as f:
            # deliberately injected step failure (role-stall /
            # straggler): the tick is lost, the work is not
            self.resil.count("fault_steps")
            self.tracer.instant("fault.injected", tick=self.tick,
                                role=self.role,
                                fault=f.fault_class)
        except kvs.OutOfPages:
            if self.resil is not None and self.alloc is not None \
                    and self.alloc.holdback > 0:
                # page-spike squeezed even the last runner; wait the
                # window out (pages come back, recompute resumes)
                self.resil.count("wait_ticks")
            else:
                raise

    # ----------------------------------------------------------- internals
    def _incomplete(self, on_incomplete: str, blocked: bool,
                    pending: Sequence[Tuple[int, Request]] = ()) -> None:
        live = [e for e in self.slot_entry if e is not None]
        live += list(self.sched.queue)
        # terminal lifecycle state for everything that never finished —
        # including arrivals still pending at max_steps exhaustion, which
        # previously left no record at all (metrics denominators lied)
        for e in live:
            if e.record is not None and e.record.get("state") == "queued":
                e.record["state"] = "unserved"
        for _, req in pending:
            self.records.append(_unserved_record(req))
        unfinished = [e.req.rid for e in live]
        unfinished += [req.rid for _, req in pending]  # never submitted
        if not unfinished or on_incomplete == "ignore":
            return
        why = ("admission blocked (page pool too small for the "
               "head-of-line request's worst-case need)" if blocked
               else "max_steps exhausted")
        msg = (f"Session.run stopped with {len(unfinished)} unfinished "
               f"request(s) {sorted(unfinished)}: {why}; "
               f"{len(self.results)} completed")
        if on_incomplete == "warn":
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
            return
        raise kvs.OutOfPages(msg) if blocked else RuntimeError(msg)

    # ------------------------------------------------------- resil layer
    def resil_summary(self) -> Optional[dict]:
        """Shed/retry/deadline-miss/fault counters, or None when the
        resilience layer is off."""
        return None if self.resil is None else self.resil.summary()

    def _fault_waiting(self) -> bool:
        """True when idleness is an injected condition (page spike), not
        an admission deadlock — the caller should burn the tick."""
        return (self.resil is not None and self.alloc is not None
                and self.alloc.holdback > 0)

    def _resil_tick(self, tick: int) -> None:
        """Per-tick policy: apply the fault plan's page holdback, expire
        deadlines, shed load past the watermark, walk the degradation
        ladder, run the watchdog audit."""
        r = self.resil
        if r.plan is not None and self.alloc is not None:
            self.alloc.holdback = r.plan.page_holdback(
                self.alloc.n_pages - 1, tick, role=self.role)
        self._expire_queue_deadlines(tick)
        self._expire_slot_deadlines(tick)
        if r.cfg.shed_watermark is not None and self.alloc is not None:
            self._shed_load()
        if r.degrade is not None and self.alloc is not None:
            usable = max(1, self.alloc.n_pages - 1)
            if r.degrade.update(self.alloc.available / usable) >= 1 \
                    and self.prefix is not None:
                self.prefix.release(self.alloc, 1)  # L1: drop LRU pins
        if r.watchdog is not None and r.watchdog.due(tick):
            r.count("watchdog_audits")
            r.watchdog.audit(self)

    def _expire_queue_deadlines(self, tick: int) -> None:
        for e in self.sched.pop_expired(tick):
            self.resil.count("deadline_miss")
            self._fail_entry(e, "deadline")

    def _expire_slot_deadlines(self, tick: int) -> None:
        for i, entry in enumerate(self.slot_entry):
            if entry is None or entry.deadline_tick is None \
                    or tick <= entry.deadline_tick:
                continue
            entry.out = list(self.slot_out[i])
            if self.kv_cache == "paged":
                self._release_slot_pages(i)
            self.slot_entry[i] = None
            self.slot_pending[i] = []
            self.slot_out[i] = []
            self.resil.count("deadline_miss")
            self._fail_entry(entry, "deadline")

    def _shed_load(self) -> None:
        """Reject never-admitted queued work, youngest first, while the
        queue's summed worst-case page need exceeds the watermark
        fraction of the usable pool."""
        r = self.resil
        limit = r.cfg.shed_watermark * max(1, self.alloc.n_pages - 1)
        total = sum(self._page_need(e) for e in self.sched.queue)
        while total > limit:
            e = self.sched.shed_youngest()
            if e is None:
                break
            total -= self._page_need(e)
            r.count("shed")
            self.tracer.instant("sched.shed", tick=self.tick,
                                role=self.role, rid=e.req.rid)
            self._fail_entry(e, "shed")

    def _fail_entry(self, entry: schd.SchedEntry, reason: str) -> None:
        """Terminal structured failure: the request leaves the system as
        a RequestFailed result, never an unhandled exception."""
        rec = entry.record
        if rec is not None:
            rec["state"] = "failed"
            rec["failed_reason"] = reason
            rec["retries"] = entry.retries
            rec["n_generated"] = len(entry.out)
        self.failed.append(rsl.RequestFailed(
            rid=entry.req.rid, reason=reason, tokens=list(entry.out),
            retries=entry.retries))
        if self.resil is not None:
            self.resil.count("failed")
        self.tracer.instant("resil.fail", tick=self.tick, role=self.role,
                            rid=entry.req.rid, reason=reason,
                            retries=entry.retries)
        # flight-recorder post-mortem: the ticks leading up to the failure
        self.tracer.crash(f"RequestFailed_{reason}",
                          rid=entry.req.rid, why=reason,
                          role=self.role, tick=self.tick)

    def _page_need(self, entry: schd.SchedEntry) -> int:
        req = entry.req
        return schd.scheduler.page_need(
            len(req.prompt) + len(entry.out), req.max_new - len(entry.out),
            self.max_len, self.page_size)

    def _prefix_hit_pids(self, entry: schd.SchedEntry) -> List[int]:
        """Page ids of the leading full prompt pages this entry could
        attach from the prefix cache right now (pure lookup, no refs)."""
        if self.prefix is None:
            return []
        n = schd.prefix.usable_prefix_pages(len(entry.req.prompt),
                                            self.page_size)
        pids: List[int] = []
        for j in range(min(n, self.host_table.shape[1])):
            pid = self.prefix.peek(entry.hashes[j])
            if pid is None:
                break
            pids.append(pid)
        return pids

    def _fits(self, entry: schd.SchedEntry) -> bool:
        if self.kv_cache != "paged":
            return True            # dense cache: slots are pre-allocated
        hits = self._prefix_hit_pids(entry)
        avail = self.alloc.available
        if self.prefix is not None:
            # cache pins can be released under pressure; count the pages
            # only the cache still holds as effectively available — but
            # NOT the pages this entry would itself attach (releasing
            # those frees nothing once the slot holds a ref)
            avail += self.prefix.releasable(self.alloc, exclude=hits)
        return self._page_need(entry) - len(hits) <= avail

    def _fill_slots(self):
        with obs_mod.span("session.admit", self.tracer,
                          step=self.stats["steps"]) as ann:
            for i in range(self.slots):
                if self.slot_entry[i] is not None:
                    continue
                entry = self.sched.next_entry(self._fits,
                                              step=self.stats["steps"])
                if entry is None:
                    break
                self._admit(i, entry)
            if any(e is not None for e in self.slot_entry):
                ann.set_metadata(kind=self._step_kind())

    def _admit(self, i: int, entry: schd.SchedEntry):
        req = entry.req
        now = time.perf_counter()
        rec = entry.record
        if rec["admit_step"] is None:
            rec["admit_step"] = self.stats["steps"]
            rec["admit_time"] = now
        if self.resil is not None and self.resil.degrade is not None \
                and self.resil.degrade.kv_demote and not rec.get("degraded"):
            # L2 degradation: this admission would get int8 KV in the next
            # session generation (pool dtype is fixed per live session)
            rec["degraded"] = True
            self.resil.count("degraded_admissions")
        self.tracer.instant("sched.admit", tick=self.tick, role=self.role,
                            slot=i, rid=req.rid,
                            resumed=len(entry.out))
        self.slot_entry[i] = entry
        # recompute resume: a preempted request re-prefills its prompt
        # PLUS its generated-so-far tokens, then continues sampling
        self.slot_pending[i] = list(req.prompt) + list(entry.out)
        self.slot_out[i] = list(entry.out)
        self._reset_slot_state(i)
        self.stats["fills"] += 1
        if self.kv_cache != "paged":
            return
        self.slot_cache_j[i] = 0
        if self.prefix is not None:
            self._attach_prefix(i, entry)

    def _attach_prefix(self, i: int, entry: schd.SchedEntry):
        """Reuse cached prefix pages: attach their ids into this slot's
        table rows and skip the covered prompt tokens."""
        n = schd.prefix.usable_prefix_pages(len(entry.req.prompt),
                                            self.page_size)
        attached: List[Tuple[int, int]] = []           # (table_j, pid)
        for j in range(min(n, self.host_table.shape[1])):
            pid = self.prefix.lookup(entry.hashes[j])
            if pid is None:
                break
            self.alloc.ref(pid)
            self.host_table[i, j] = pid
            attached.append((j, pid))
        if not attached:
            return
        pj = jnp.asarray([a[0] for a in attached], jnp.int32)
        pids = jnp.asarray([a[1] for a in attached], jnp.int32)
        self._h2d += pj.nbytes + pids.nbytes
        self.state["page_table"] = \
            self.state["page_table"].at[i, pj].set(pids)
        skip = len(attached) * self.page_size
        self.slot_pending[i] = self.slot_pending[i][skip:]
        self.slot_pos[i] = skip
        self.state["pos"] = self.state["pos"].at[i].set(skip)
        self.slot_cache_j[i] = len(attached)
        entry.prefix_pages += len(attached)
        entry.record["prefix_pages"] += len(attached)
        self.stats["prefix_hits"] += 1
        self.stats["prefix_pages_reused"] += len(attached)
        self.stats["pages_in_use"] = self.alloc.in_use

    def _reset_slot_state(self, i: int):
        def zero_slot(x):
            if x.ndim >= 2 and x.shape[1] == self.slots:  # [L, B, ...]
                return x.at[:, i].set(jnp.zeros_like(x[:, i]))
            return x
        if self.kv_cache == "paged":
            # pool pages are shared, not slot-indexed: release the slot's
            # pages (idempotent — already freed at request completion) and
            # zero only the slot-shaped leaves (mamba conv/h etc.).  Stale
            # page contents are harmless: the position mask never reaches
            # unwritten slots and scales reset on re-allocation.
            self._release_slot_pages(i)
            layers = dict(self.state["layers"])
            kv = layers.pop("kv")
            layers = jax.tree.map(zero_slot, layers)
            layers["kv"] = kv
            self.state = {"layers": layers,
                          "pos": self.state["pos"].at[i].set(0),
                          "page_table": self.state["page_table"]}
            self.slot_pos[i] = 0
            return
        layers = jax.tree.map(zero_slot, self.state["layers"])
        pos = self.state["pos"].at[i].set(0)
        # empty cache slots must read as "never written": pos fields are -1
        if self.cfg.family not in ("rwkv6",):
            layers = dict(layers)
            kv = layers["kv"]
            layers["kv"] = kv._replace(
                pos=kv.pos.at[:, i].set(-jnp.ones_like(kv.pos[:, i])))
        self.state = {"layers": layers, "pos": pos}

    # ------------------------------------------------------ paged KV admin
    def _release_slot_pages(self, i: int) -> None:
        """Free every page owned by slot ``i`` (request done / slot reset /
        preemption).  Shared prefix pages just lose this slot's ref."""
        pages = [int(p) for p in self.host_table[i] if p >= 0]
        if not pages:
            return
        self.alloc.free(pages)
        self.host_table[i] = -1
        self.state["page_table"] = self.state["page_table"].at[i].set(
            jnp.int32(kvs.NO_PAGE))
        self.stats["pages_in_use"] = self.alloc.in_use

    def _preempt_slot(self, i: int) -> None:
        """Evict slot ``i`` back to the queue front: pages freed now,
        tokens regenerated on re-admission (recompute resume)."""
        entry = self.slot_entry[i]
        entry.out = list(self.slot_out[i])
        entry.record["preemptions"] += 1
        self.tracer.instant("sched.preempt", tick=self.tick,
                            role=self.role, slot=i, rid=entry.req.rid,
                            generated=len(entry.out))
        self._release_slot_pages(i)
        self.slot_entry[i] = None
        self.slot_pending[i] = []
        self.slot_out[i] = []
        self.sched.requeue(entry)
        self.stats["preemptions"] += 1

    def _ensure_pages(self, counts: List[int]) -> int:
        """Host-side page faults: before a step, make sure each active
        slot owns every page its next ``counts[i]`` tokens land in; fresh
        pages get their quantization scales cleared so stale maxima can't
        poison them.  Returns the number of pages granted."""
        npp = self.host_table.shape[1]
        events = []
        try:
            for i, entry in enumerate(self.slot_entry):
                if entry is None or counts[i] == 0:
                    continue
                lo = self.slot_pos[i] // self.page_size
                hi = (self.slot_pos[i] + counts[i] - 1) // self.page_size
                for pi in range(lo, min(hi, npp - 1) + 1):
                    if pi >= npp or self.host_table[i, pi] >= 0:
                        continue   # beyond max_len (clamped) / present
                    pid = self.alloc.alloc()
                    self.host_table[i, pi] = pid
                    events.append((i, pi, pid))
        except kvs.OutOfPages:
            # transactional: roll back this round's host-side grants so a
            # caller that drains requests and retries never sees a page
            # recorded host-side but absent from the device table
            for i, pi, pid in events:
                self.host_table[i, pi] = -1
            self.alloc.free(pid for _, _, pid in events)
            raise
        if not events:
            return 0
        si, pi, pids = (jnp.asarray([e[n] for e in events], jnp.int32)
                        for n in range(3))
        self._h2d += si.nbytes + pi.nbytes + pids.nbytes
        self.state["page_table"] = \
            self.state["page_table"].at[si, pi].set(pids)
        kv = self.state["layers"]["kv"]
        if kv.k_scale is not None:
            kv = kv._replace(k_scale=kv.k_scale.at[:, pids].set(0.0),
                             v_scale=kv.v_scale.at[:, pids].set(0.0))
            layers = dict(self.state["layers"])
            layers["kv"] = kv
            self.state["layers"] = layers
        self.stats["page_allocs"] = self.alloc.total_allocs
        self.stats["pages_in_use"] = self.alloc.in_use
        self.stats["pages_peak"] = self.alloc.peak
        return len(events)

    def _ensure_pages_or_preempt(self, counts: List[int]) -> int:
        """Resolve page pressure: allocate; on OutOfPages release prefix
        pins LRU-first, then preempt the youngest slot, until the
        remaining batch fits.  The last runner is never preempted — a
        pool too small for a single request still raises.  Returns the
        number of pages granted."""
        while True:
            try:
                return self._ensure_pages(counts)
            except kvs.OutOfPages:
                if self.prefix is not None \
                        and self.prefix.release(self.alloc, 1):
                    continue
                victim = schd.Scheduler.choose_victim(self.slot_entry)
                if victim is None:
                    raise
                self._preempt_slot(victim)
                counts[victim] = 0

    def _reclaim_swa_pages(self) -> None:
        """On pure-SWA archs, free pages that slid fully behind the widest
        layer window — decode memory stays O(window), page-granular."""
        if self._swa_window is None:
            return
        events = []
        for i, entry in enumerate(self.slot_entry):
            if entry is None:
                continue
            dead = kvs.reclaimable_prefix(self.slot_pos[i],
                                          self._swa_window, self.page_size)
            for pi in range(min(dead, self.host_table.shape[1])):
                pid = int(self.host_table[i, pi])
                if pid >= 0:
                    self.alloc.free([pid])
                    self.host_table[i, pi] = -1
                    events.append((i, pi))
        if not events:
            return
        si = jnp.asarray([e[0] for e in events], jnp.int32)
        pi = jnp.asarray([e[1] for e in events], jnp.int32)
        self._h2d += si.nbytes + pi.nbytes
        self.state["page_table"] = self.state["page_table"].at[si, pi].set(
            jnp.int32(kvs.NO_PAGE))
        self.stats["pages_reclaimed_swa"] += len(events)
        self.stats["pages_in_use"] = self.alloc.in_use

    def _insert_slot_prefix(self, i: int, entry: schd.SchedEntry) -> None:
        """Pin slot ``i``'s freshly-completed full prompt pages into the
        prefix cache (first writer wins; generated-token pages are never
        cached).  Also called by the disagg prefill role right before a
        handoff, when the slot's entry reference is already detached."""
        n_full = len(entry.req.prompt) // self.page_size
        j = self.slot_cache_j[i]
        while j < min(n_full, self.host_table.shape[1]) \
                and self.slot_pos[i] >= (j + 1) * self.page_size:
            pid = int(self.host_table[i, j])
            if pid >= 0:           # may be gone (SWA reclamation)
                self.prefix.insert(entry.hashes[j], pid, self.alloc)
            j += 1
        self.slot_cache_j[i] = j

    def _insert_prefix_pages(self) -> None:
        if self.prefix is None:
            return
        for i, entry in enumerate(self.slot_entry):
            if entry is not None:
                self._insert_slot_prefix(i, entry)

    # ------------------------------------------------------------ stepping
    def _step_kind(self) -> str:
        """The next model call: ``chunked`` while an active slot still
        has prompt to feed (chunked prefill on), else ``decode``."""
        if self.chunk > 1 and any(self.slot_pending[i]
                                  for i, e in enumerate(self.slot_entry)
                                  if e is not None):
            return "chunked"
        return "decode"

    def _advance(self):
        """One model call.  Its host work sits in ``session.*`` spans
        (obs.span) that carry the call's ``step`` (model calls before
        it) and ``kind``, and it leaves one record in
        :attr:`step_records`."""
        if self.resil is not None and self.resil.plan is not None:
            # fault seam: a stalled/straggling role loses the whole tick
            # (raises InjectedFault before any state is touched)
            self.resil.plan.check_step(self.role, self.tick)
        args = {"step": self.stats["steps"], "kind": self._step_kind()}
        if self._step_ann is not None:
            self._step_ann.set_metadata(kind=args["kind"])
        step = self._advance_chunked if args["kind"] == "chunked" \
            else self._advance_decode
        counts, granted, d2h = step(args)
        if self.kv_cache == "paged" and (self._swa_window is not None
                                         or self.prefix is not None):
            with obs_mod.span("session.pages", self.tracer, **args):
                self._reclaim_swa_pages()
                self._insert_prefix_pages()
        self.step_records.append({
            **args, "tokens": sum(counts),
            "sampled": len(self._emitted),
            "active": sum(1 for c in counts if c),
            "pages_granted": granted, "d2h_bytes": d2h,
            "h2d_bytes": self._h2d, "emitted": self._emitted})
        self._h2d = 0
        self._emitted = []

    def _active_counts(self, chunk: int) -> List[int]:
        counts = [0] * self.slots
        for i, entry in enumerate(self.slot_entry):
            if entry is None:
                continue
            counts[i] = min(chunk, len(self.slot_pending[i])) \
                if self.slot_pending[i] else 1
        return counts

    def _advance_decode(self, args: dict) -> Tuple[List[int], int, int]:
        """One token per active slot through the backend's decode step;
        returns (tokens fed per slot, pages granted, logits bytes read
        back)."""
        counts = self._active_counts(1)
        granted = 0
        if self.kv_cache == "paged":
            with obs_mod.span("session.pages", self.tracer, **args):
                granted = self._ensure_pages_or_preempt(counts)
        with obs_mod.span("session.feed", self.tracer, **args):
            tokens = np.zeros((self.slots,), np.int32)
            for i, entry in enumerate(self.slot_entry):
                if entry is None:
                    continue
                if self.slot_pending[i]:
                    tokens[i] = self.slot_pending[i][0]
                elif self.slot_out[i]:
                    tokens[i] = self.slot_out[i][-1]
                else:
                    tokens[i] = entry.req.prompt[-1]
            fed = jnp.asarray(tokens)
            self._h2d += tokens.nbytes
        with obs_mod.span("session.dispatch", self.tracer, **args):
            self.state, logits = self._step(self.params, self.state, fed)
        self.stats["steps"] += 1
        self.tracer.span("step.decode", tick=self.tick, role=self.role,
                         active=sum(1 for c in counts if c),
                         step=self.stats["steps"])
        now = time.perf_counter()
        if self.kv_cache == "paged":
            for i, entry in enumerate(self.slot_entry):
                if entry is not None:
                    self.slot_pos[i] += 1
        with obs_mod.span("session.readback", self.tracer, **args):
            logits = np.asarray(logits[:, : self.cfg.vocab])
        with obs_mod.span("session.sample", self.tracer, **args):
            for i, entry in enumerate(self.slot_entry):
                if entry is None:
                    continue
                if self.slot_pending[i]:
                    self.slot_pending[i].pop(0)
                    if self.slot_pending[i]:
                        continue  # still prefilling
                self._emit(i, logits[i], now)
        return counts, granted, logits.nbytes

    def _advance_chunked(self, args: dict) -> Tuple[List[int], int, int]:
        """Mixed prefill+decode step: up to ``chunk`` prompt tokens per
        prefilling slot, 1 token per decoding slot, all in one call;
        returns what :meth:`_advance_decode` does."""
        counts = self._active_counts(self.chunk)
        with obs_mod.span("session.pages", self.tracer, **args):
            granted = self._ensure_pages_or_preempt(counts)
        with obs_mod.span("session.feed", self.tracer, **args):
            tokens = np.zeros((self.slots, self.chunk), np.int32)
            for i, entry in enumerate(self.slot_entry):
                if entry is None:
                    continue
                if self.slot_pending[i]:
                    k = counts[i]
                    tokens[i, :k] = self.slot_pending[i][:k]
                elif self.slot_out[i]:
                    tokens[i, 0] = self.slot_out[i][-1]
                else:
                    tokens[i, 0] = entry.req.prompt[-1]
            n_tok = np.asarray(counts, np.int32)
            fed = (jnp.asarray(tokens), jnp.asarray(n_tok))
            self._h2d += tokens.nbytes + n_tok.nbytes
        with obs_mod.span("session.dispatch", self.tracer, **args):
            self.state, logits = self._prefill(self.params, self.state,
                                               *fed)
        self.stats["steps"] += 1
        self.tracer.span("step.prefill", tick=self.tick, role=self.role,
                         active=sum(1 for c in counts if c),
                         tokens=sum(counts), step=self.stats["steps"])
        now = time.perf_counter()
        for i, entry in enumerate(self.slot_entry):
            if entry is not None:
                self.slot_pos[i] += counts[i]
        with obs_mod.span("session.readback", self.tracer, **args):
            logits = np.asarray(logits[:, : self.cfg.vocab])
        with obs_mod.span("session.sample", self.tracer, **args):
            for i, entry in enumerate(self.slot_entry):
                if entry is None:
                    continue
                if self.slot_pending[i]:
                    del self.slot_pending[i][:counts[i]]
                    if self.slot_pending[i]:
                        continue  # still prefilling
                self._emit(i, logits[i], now)
        return counts, granted, logits.nbytes

    def _emit(self, i: int, logits_i: np.ndarray, now: float):
        """Sample the next token for slot ``i`` from this step's logits;
        finish the request when max_new is reached."""
        entry = self.slot_entry[i]
        req = entry.req
        if req.temperature > 0:
            self.key, sub = jax.random.split(self.key)
            nxt = int(jax.random.categorical(
                sub, jnp.asarray(logits_i) / req.temperature))
        else:
            nxt = int(logits_i.argmax())
        self.slot_out[i].append(nxt)
        self._emitted.append((req.rid, nxt))
        rec = entry.record
        if rec["first_token_time"] is None:
            rec["first_token_time"] = now
            rec["first_token_step"] = self.stats["steps"]
            self.tracer.instant("req.first_token", tick=self.tick,
                                role=self.role, slot=i, rid=req.rid)
        if len(self.slot_out[i]) >= req.max_new:
            self.results.append(Result(req.rid, self.slot_out[i]))
            rec["finish_time"] = now
            rec["n_generated"] = len(self.slot_out[i])
            rec["state"] = "completed"
            self.tracer.instant("req.finish", tick=self.tick,
                                role=self.role, slot=i, rid=req.rid,
                                tokens=len(self.slot_out[i]))
            self.slot_entry[i] = None
            if self.kv_cache == "paged":
                # return pages eagerly — don't wait for a refill
                self._release_slot_pages(i)
