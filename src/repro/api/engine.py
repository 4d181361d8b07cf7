"""`Engine` — THE entry point to compress, load, run and benchmark a model.

One object, four backends::

    from repro.api import Engine, Request, CompressionSpec

    eng = Engine("llama3-8b-smoke-cfg-or-ArchConfig")      # random init
    eng.compress(CompressionSpec(mode="aida", density=0.25))
    results = eng.serve([Request(prompt=[1, 2, 3], max_new=8)])
    est = eng.estimate(backend="cycle-sim", workload="alexnet-fc")

`compress()` returns the engine for chaining; serving goes through a
continuous-batching `Session` compiled by the active backend; `estimate()`
routes to any cycle-accounting backend (`ap-emulator`, `cycle-sim`).
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence, Union

import numpy as np

from repro.api import compress as compress_mod
from repro.api.registry import CapabilityError, Executor, get_backend
from repro.api.session import Request, Result, Session
from repro.api.spec import CompressionSpec, FCProblem
from repro.configs.base import ArchConfig

#: the declared SLO the `capacity` BENCH section gates against —
#: scheduler-tick units (deterministic), calibrated so the burst preset
#: separates under-provisioned from adequate configs: 2 slots queues to
#: ttft_p99≈36 ticks, 4 slots reaches ≈3
CAPACITY_SLO = "ttft_p99=20,tpot_p99=4,goodput=1.0"

#: the 2-point smoke sweep (capacity.py --smoke and the BENCH section):
#: an under-provisioned config the SLO rejects and an adequate one
CAPACITY_SMOKE_SWEEP = (
    {"slots": 2, "kv_pool_pages": 16, "chunk": 4, "policy": "fifo"},
    {"slots": 4, "kv_pool_pages": 24, "chunk": 4, "policy": "fifo"},
)


def _spec_modes(spec: CompressionSpec) -> set:
    """Modes a spec actually executes ('skip' leaves leaves dense/raw)."""
    return {spec.mode} | {m for m in spec.overrides.values() if m != "skip"}


class Engine:
    def __init__(self, cfg: Union[ArchConfig, str, None] = None,
                 params=None, *, backend: Optional[str] = None,
                 seed: int = 0):
        if isinstance(cfg, str):
            from repro.configs import get
            cfg = get(cfg)
        self.cfg = cfg
        self._params = params
        self._backend_name = backend
        self._seed = seed
        self.compression: Optional[CompressionSpec] = None
        self.stats: Optional[dict] = None

    # -------------------------------------------------------------- state
    @property
    def params(self):
        """Model params (random-initialized on first access if not given)."""
        if self._params is None:
            if self.cfg is None:
                raise ValueError("Engine has no cfg; pass params explicitly "
                                 "or construct with an ArchConfig")
            import jax
            from repro.models import model as M
            self._params = M.init_params(self.cfg,
                                         jax.random.PRNGKey(self._seed))
        return self._params

    @property
    def backend(self) -> Executor:
        """Active decode backend: explicit choice, else 'pallas' once
        compressed to a non-dense mode, else 'jax-dense'."""
        if self._backend_name:
            return get_backend(self._backend_name)
        if self.compression is not None \
                and _spec_modes(self.compression) - {"dense"}:
            return get_backend("pallas")
        return get_backend("jax-dense")

    # ---------------------------------------------------------- compress
    def compress(self, spec: Union[CompressionSpec, str, None] = None,
                 *, verbose=None, **kw) -> "Engine":
        """Deep-Compression of every eligible projection (prune -> share ->
        pack) per `spec`; keyword shortcuts (mode=, density=, k=) also work.
        Returns self for chaining; stats land in `self.stats`."""
        spec = CompressionSpec.coerce(spec)
        if kw:
            import dataclasses
            spec = dataclasses.replace(spec, **kw)
        if self._backend_name:  # explicit pin: the backend must run the modes
            caps = self.backend.caps
            wanted = _spec_modes(spec)
            if len(wanted) > 1 and not caps.per_layer_override:
                raise CapabilityError(
                    f"backend {self._backend_name!r} does not support "
                    "per-layer mode overrides")
            missing = wanted - set(caps.modes)
            if missing:
                raise CapabilityError(
                    f"backend {self._backend_name!r} cannot execute modes "
                    f"{sorted(missing)}; its modes are {caps.modes} "
                    "(drop the explicit backend= pin to auto-route)")
        self._params, self.stats = compress_mod.compress_params(
            self.params, spec, verbose=verbose)
        self.compression = spec
        return self

    # ------------------------------------------------------------- serve
    def _pretune(self, batch_slots: int, max_len: int, page_size: int,
                 kv_dtype: Optional[str], kv_cache: Optional[str],
                 plan, scheduler=None) -> None:
        """Autotune the kernels a session at this batch width will trace:
        compressed-FC geometries (shard-local under a plan) and the
        paged-attention decode + chunked-prefill impl/tile choices."""
        from repro.kernels import ops, tune
        tp = plan.tp if plan is not None else 1
        if self.backend.name == "pallas" and self.compression is not None:
            if tune.enabled():
                if tp > 1:
                    # the sharded step only looks up shard-LOCAL
                    # geometries; tuning the global ones would be
                    # wasted session-startup wall-clock
                    from repro import shard as shardmod
                    shardmod.tune_local_views(self.params, plan,
                                              batch_slots,
                                              ops.pallas_interpret())
                else:
                    tune.tune_params(self.params, batch_slots,
                                     ops.pallas_interpret())
        import repro.api.session as sess_mod
        resolved_kv = sess_mod.resolve_kv_cache(kv_cache, self.cfg)
        # mesh sessions resolve the paged kernels with the GLOBAL
        # geometry (shard.paged_attention_*_sharded pins the choice
        # before entering shard_map), so the same global tune applies
        # whether heads are sharded or whole
        if resolved_kv == "paged" and self.cfg.family != "rwkv6" \
                and tune.enabled():
            kvd = kv_dtype or sess_mod.KV_DTYPE_DEFAULT
            interp = ops.pallas_interpret()
            tune.tune_paged(self.cfg, batch_slots, max_len, page_size,
                            kvd, interp)
            from repro import sched as schd
            chunk = schd.SchedConfig.coerce(scheduler).chunk
            if chunk > 1 and schd.supports_chunked_prefill(self.cfg):
                tune.tune_paged_chunk(self.cfg, batch_slots, max_len,
                                      page_size, chunk, kvd, interp)

    def session(self, batch_slots: int = 4, max_len: int = 256,
                seed: int = 0, kv_cache: Optional[str] = None,
                page_size: int = 16,
                kv_pool_pages: Optional[int] = None,
                kv_dtype: Optional[str] = None,
                scheduler=None, mesh=None, disagg=None, resil=None,
                obs=None):
        """A continuous-batching serving session on the active backend.

        ``scheduler``: a sched.SchedConfig (or dict / policy name) —
        admission policy, prefill chunk width, prefix caching.

        ``mesh``: a jax Mesh with a ``model`` axis — serving goes
        tensor-parallel on an explicit `repro.shard.ShardingPlan`:
        compressed FC runs shard-local (each device owns a band of row
        blocks / output channels), KV pools shard their head axis, and
        the decode / chunked-prefill steps compile with input/output
        shardings.  ``mesh=None`` (default) is the unchanged
        single-device path.

        ``disagg``: True / dict / `repro.disagg.DisaggConfig` — build a
        disaggregated prefill/decode session pair instead (returns a
        `repro.disagg.DisaggSession` with the same submit/run surface):
        two roles sharing this engine's params, each with its own slots
        and page pool, connected by the page-migration channel.  With
        ``prefill_devices``/``decode_devices`` set, the roles run
        tensor-parallel on disjoint device meshes
        (launch.mesh.make_role_meshes); ``batch_slots`` and
        ``kv_pool_pages`` are ignored in favor of the per-role knobs.
        Mutually exclusive with ``mesh``.

        On the Pallas backend, every unique compressed-FC geometry is
        autotuned for this batch width *before* the decode step compiles,
        so the jitted step traces against the winning tiles
        (kernels.tune; disable with REPRO_AUTOTUNE=0).  A paged-KV
        session additionally pre-tunes the paged-attention decode and
        chunked-prefill impl/tile choices for this (geometry, batch,
        chunk, backend) — mesh sessions included, since the shard_map
        wrappers pin the globally-resolved choice — and a mesh session
        tunes the *shard-local* FC geometries its shard_map kernels run.

        ``resil``: a `repro.resil.ResilConfig` (or dict / ``"preset:seed"``
        fault-plan string) — deterministic fault injection, request
        deadlines, bounded retry, load shedding, and graceful
        degradation.  Passing a live `ResilState` carries the degradation
        ladder across session generations: when sustained page pressure
        has pushed it to L2, this session's KV pool is demoted to int8.
        ``resil=None`` (default) is the exact pre-resil serving path.

        ``obs``: a `repro.obs.Tracer` — structured event tracing across
        every serving seam (admission, preemption, prefill/decode steps,
        handoffs, allocator, prefix cache, fault injections), exportable
        as a Chrome/Perfetto timeline.  ``obs=None`` (default) traces
        nothing at zero cost.
        """
        if self.cfg is None:
            raise ValueError("serving needs an ArchConfig")
        if resil is not None:
            from repro import resil as rsl
            if isinstance(resil, rsl.ResilState):
                # next-session degradation boundary: pool dtype is fixed
                # for a live session, so L2 demotion lands here
                kv_dtype = resil.next_kv_dtype(kv_dtype)
        backend = self.backend
        if not backend.caps.batched_decode:
            raise CapabilityError(
                f"backend {backend.name!r} cannot serve (no batched decode)")
        if disagg is not None and disagg is not False:
            if mesh is not None:
                raise ValueError(
                    "mesh= and disagg= are mutually exclusive — give the "
                    "roles their own devices via DisaggConfig."
                    "prefill_devices/decode_devices")
            if kv_cache not in (None, "auto", "paged"):
                raise ValueError(
                    "disaggregated serving migrates KV pages; it cannot "
                    f"run on kv_cache={kv_cache!r}")
            from repro.disagg import DisaggConfig, DisaggSession
            d = DisaggConfig.coerce(disagg)
            pre_plan = dec_plan = None
            if d.prefill_devices is not None:
                from repro import shard as shardmod
                from repro.launch.mesh import make_role_meshes
                pre_mesh, dec_mesh = make_role_meshes(
                    d.prefill_devices, d.decode_devices)
                pre_plan = shardmod.make_plan(pre_mesh, self.cfg)
                dec_plan = shardmod.make_plan(dec_mesh, self.cfg)
            self._pretune(d.prefill_slots, max_len, page_size, kv_dtype,
                          "paged", pre_plan, scheduler=scheduler)
            if d.decode_slots != d.prefill_slots or \
                    dec_plan is not pre_plan:
                self._pretune(d.decode_slots, max_len, page_size,
                              kv_dtype, "paged", dec_plan,
                              scheduler=scheduler)
            return DisaggSession(
                self.cfg, self.params, disagg=d, max_len=max_len,
                seed=seed, backend=backend, page_size=page_size,
                kv_dtype=kv_dtype, scheduler=scheduler,
                prefill_plan=pre_plan, decode_plan=dec_plan, resil=resil,
                obs=obs)
        plan = None
        if mesh is not None:
            from repro import shard as shardmod
            plan = shardmod.make_plan(mesh, self.cfg)
        self._pretune(batch_slots, max_len, page_size, kv_dtype,
                      kv_cache, plan, scheduler=scheduler)
        return Session(self.cfg, self.params, batch_slots=batch_slots,
                       max_len=max_len, seed=seed, backend=backend,
                       kv_cache=kv_cache, page_size=page_size,
                       kv_pool_pages=kv_pool_pages, kv_dtype=kv_dtype,
                       scheduler=scheduler, plan=plan, resil=resil,
                       obs=obs)

    def serve(self, requests: Sequence[Union[Request, List[int]]],
              *, batch_slots: int = 4, max_len: int = 256,
              max_steps: int = 10_000, seed: int = 0,
              kv_cache: Optional[str] = None,
              scheduler=None, disagg=None, resil=None,
              obs=None) -> List[Result]:
        """Serve a batch of requests to completion (continuous batching).
        Results come back in deterministic rid order.  ``disagg`` routes
        through a disaggregated prefill/decode session pair — greedy
        results are token-identical either way.  ``resil`` activates the
        resilience layer (deadlines/retry/fault injection)."""
        sess = self.session(batch_slots=batch_slots, max_len=max_len,
                            seed=seed, kv_cache=kv_cache,
                            scheduler=scheduler, disagg=disagg,
                            resil=resil, obs=obs)
        for rid, req in enumerate(requests):
            if not isinstance(req, Request):
                req = Request(prompt=list(req), rid=rid)
            sess.submit(req)
        return sess.run(max_steps=max_steps)

    # ---------------------------------------------------------- estimate
    def estimate(self, backend: str = "cycle-sim",
                 workload: Union[FCProblem, str, Sequence, None] = None,
                 **kw) -> dict:
        """Cycle/perf accounting through a cost-model backend.

        `workload`: an FCProblem (concrete FC instance; 'ap-emulator'
        measures it bit-level, 'cycle-sim' prices it closed-form — the two
        agree exactly under the EMULATOR microcode), or a named network
        ('alexnet-fc', 'ctc-lstm', 'table1') for 'cycle-sim'.
        """
        ex = get_backend(backend)
        if not ex.caps.cycle_accounting:
            raise CapabilityError(
                f"backend {backend!r} has no cycle accounting")
        if workload is None:
            workload = "alexnet-fc"
        return ex.estimate(workload, **kw)

    # --------------------------------------------------------- benchmark
    def kv_benchmark(self, mode: str = "aida", requests: int = 8,
                     max_new: int = 24, batch_slots: int = 2,
                     max_len: int = 64, page_size: int = 16,
                     density: float = 0.25) -> dict:
        """Paged-vs-dense KV cache comparison on one compressed mode:
        serve the same request mix through both cache kinds (step-time
        parity check), record KV bytes/token, and micro-time the
        attention-vs-FC split of a decode step (the share the paged
        subsystem exists to attack)."""
        from repro import kvstore as kvs
        from repro.kernels import tune
        cfg = self.cfg
        if cfg is None or cfg.family == "rwkv6":
            raise CapabilityError(
                "kv_benchmark needs an attention arch (rwkv6 has no KV "
                "cache to page)")
        eng = Engine(cfg, params=self.params)
        if mode != "dense":
            eng.compress(CompressionSpec(mode=mode, density=density),
                         verbose=None)
        reqs = [Request(prompt=[1, 2 + i % 7, 3], max_new=max_new, rid=i)
                for i in range(requests)]
        out = {"mode": mode, "page_size": page_size, "max_len": max_len,
               "batch_slots": batch_slots}
        seen_tiles = set(tune.snapshot())
        # interleaved best-of rounds: the paged/full ratio is only
        # host-speed-invariant if both sides see the same load, so
        # alternate them and keep each side's best pass
        for rnd in range(3):
            for kind in ("full", "paged"):
                # int8 pages explicitly: this section reports
                # "paged_int8" bytes/token, so the measured pool must be
                # int8 regardless of the (bf16) serving default
                sess = eng.session(batch_slots=batch_slots,
                                   max_len=max_len, kv_cache=kind,
                                   page_size=page_size, kv_dtype="int8")
                sess.submit(Request(prompt=[1], max_new=1, rid=-1))
                sess.run()  # warm the compiled step
                sess.results.clear()
                for r in reqs:
                    sess.submit(r)
                t0 = time.perf_counter()
                res = sess.run()
                dt = time.perf_counter() - t0
                n_tok = sum(len(r.tokens) for r in res)
                if kind in out and out[kind]["tok_per_s"] >= n_tok / dt:
                    continue
                rec = {"tokens": n_tok, "seconds": round(dt, 4),
                       "tok_per_s": round(n_tok / dt, 2)}
                if kind == "paged":
                    rec["pages_peak"] = sess.stats["pages_peak"]
                    rec["page_allocs"] = sess.stats["page_allocs"]
                    snap = tune.snapshot()
                    rec["tiles"] = {k: v for k, v in snap.items()
                                    if k not in seen_tiles}
                out[kind] = rec
        out["paged_over_full"] = round(
            out["paged"]["tok_per_s"] / out["full"]["tok_per_s"], 3)
        pbt = kvs.kv_bytes_per_token(cfg.n_kv, cfg.head_dim,
                                     page_size) * cfg.n_layers
        dbt = kvs.dense_kv_bytes_per_token(cfg.n_kv,
                                           cfg.head_dim) * cfg.n_layers
        out["kv_bytes_per_token"] = {
            "paged_int8": round(pbt, 1), "dense_bf16": round(dbt, 1),
            "ratio": round(pbt / dbt, 4)}
        out["attn_time_share"] = self._attn_fc_share(
            eng, batch_slots, max_len, page_size)
        return out

    def _attn_fc_share(self, eng: "Engine", batch: int, max_len: int,
                       page_size: int) -> dict:
        """Micro-decomposition of a decode step at full cache occupancy:
        attention term (cache update + attend, per layer x L) vs the FC
        term (every compressed projection at this batch width).  Shares
        are from best-of timings of the jitted pieces — the honest signal
        behind 'attention is now the dominant share' (ROADMAP)."""
        import functools

        import jax
        from repro import kvstore as kvs
        from repro.core import sparse_fc as sfc
        from repro.kernels import tune
        from repro.models import attention as attn
        from repro.models import kvcache as kvc
        from repro.obs import timeit as _timeit
        import jax.numpy as jnp
        cfg = self.cfg
        rng = np.random.default_rng(0)
        timeit = functools.partial(_timeit, reps=5, inner=3)

        hkv, h, dh = cfg.n_kv, cfg.n_heads, cfg.head_dim
        scale = dh ** -0.5
        q = jnp.asarray(rng.normal(size=(batch, h, 1, dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(batch, hkv, 1, dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(batch, hkv, 1, dh)), jnp.float32)
        cur = jnp.full((batch,), max_len - 1, jnp.int32)
        cache = kvc.init_cache(batch, hkv, max_len, dh)
        cache = cache._replace(pos=jnp.broadcast_to(
            jnp.arange(max_len, dtype=jnp.int32)[None], (batch, max_len)))
        t_full = timeit(jax.jit(
            lambda c, qq, kk, vv, p: attn.decode_attend(
                c, qq, kk, vv, p, window=jnp.int32(-1), scale=scale)[1]),
            cache, q, k, v, cur)
        npp = -(-max_len // page_size)
        pool = kvs.init_pool(1 + batch * npp, hkv, page_size, dh)
        pool = pool._replace(
            k_scale=jnp.ones_like(pool.k_scale),
            v_scale=jnp.ones_like(pool.v_scale),
            k_pages=jnp.asarray(rng.integers(
                -127, 128, pool.k_pages.shape), jnp.int8),
            v_pages=jnp.asarray(rng.integers(
                -127, 128, pool.v_pages.shape), jnp.int8))
        table = jnp.asarray(
            1 + np.arange(batch * npp).reshape(batch, npp), jnp.int32)
        t_paged = timeit(jax.jit(
            lambda pl, qq, kk, vv, p: attn.decode_attend_paged(
                jax.tree.map(lambda a: a[None], pl), 0, table, qq, kk, vv,
                p, window=jnp.int32(-1), scale=scale)[1]),
            pool, q, k, v, cur)
        # FC term: every compressed projection leaf, layer-0 view x L
        t_fc = 0.0

        def visit(leaf):
            nonlocal t_fc
            if isinstance(leaf, sfc.CompressedFC):
                lay = tune._layer0_view(leaf)
                x = jnp.asarray(rng.normal(size=(batch, lay.shape[1])),
                                jnp.float32)
                t_fc += timeit(jax.jit(
                    lambda xx: sfc.apply_fc(lay, xx)), x) * cfg.n_layers
            elif getattr(leaf, "ndim", 0) == 3:   # raw [L, d_in, d_out]
                w = leaf[0]
                x = jnp.asarray(rng.normal(size=(batch, w.shape[0])),
                                jnp.float32)
                t_fc += timeit(jax.jit(
                    lambda xx, ww: jnp.matmul(xx, ww)), x, w) \
                    * cfg.n_layers
            return leaf

        jax.tree_util.tree_map(
            visit, eng.params["layers"],
            is_leaf=lambda x: isinstance(x, sfc.CompressedFC))
        a_full, a_paged = t_full * cfg.n_layers, t_paged * cfg.n_layers
        return {"attn_us_full": round(a_full * 1e6, 1),
                "attn_us_paged": round(a_paged * 1e6, 1),
                "fc_us": round(t_fc * 1e6, 1),
                "full": round(a_full / max(a_full + t_fc, 1e-12), 4),
                "paged": round(a_paged / max(a_paged + t_fc, 1e-12), 4)}

    def serving_benchmark(self, mode: str = "aida", density: float = 0.25,
                          chunk: int = 8, page_size: int = 8,
                          max_len: int = 64) -> dict:
        """The `"serving"` section of BENCH_api.json: what the sched
        subsystem buys, measured on one compressed mode.

        Four sub-benches (deterministic step-count facts carry the CI
        assertions; wall-clock numbers are the host-noisy trajectory):

        * ``prefill`` — model calls to first token for one long prompt,
          chunked vs token-by-token (the ceil(P/C)+1 acceptance bound);
        * ``throughput`` — heterogeneous continuous batching (poisson
          arrivals, mixed lengths): tok/s, goodput, TTFT/TPOT p50-p99;
        * ``prefix`` — shared-prefix workload through the prefix cache:
          page hits and zero-leak drain;
        * ``preemption`` — a pool sized below the workload's worst case:
          completes via youngest-first preemption instead of OutOfPages.
        """
        import math

        from repro import sched as schd
        from repro.kernels import tune
        cfg = self.cfg
        if cfg is None or cfg.family == "rwkv6":
            raise CapabilityError(
                "serving_benchmark needs a paged-KV arch (rwkv6 is "
                "attention-free)")
        seen_tiles = set(tune.snapshot())
        eng = Engine(cfg, params=self.params)
        if mode != "dense":
            eng.compress(CompressionSpec(mode=mode, density=density),
                         verbose=None)
        out = {"mode": mode, "chunk": chunk, "page_size": page_size,
               "policy": "fifo"}

        def run_session(arrivals, *, slots=4, pool=None, sched_cfg=None):
            sess = eng.session(batch_slots=slots, max_len=max_len,
                               kv_cache="paged", page_size=page_size,
                               kv_pool_pages=pool, scheduler=sched_cfg)
            t0 = time.perf_counter()
            res = sess.run_workload(arrivals)
            dt = time.perf_counter() - t0
            return sess, res, dt

        # warm the compiled steps at the prefill section's batch shape so
        # recorded TTFT measures scheduling, not XLA compilation
        run_session([(0, Request(prompt=[1] * (chunk + 1), max_new=1,
                                 rid=-1))],
                    slots=2, sched_cfg={"chunk": chunk})

        # --- chunked prefill: calls to first token, long prompt --------
        plen = 3 * chunk
        prompt = [1 + (i % (self.cfg.vocab - 1)) for i in range(plen)]
        pf = {"prompt_len": plen,
              "bound_calls": math.ceil(plen / chunk) + 1}
        for label, c in (("chunked", chunk), ("one_token", 1)):
            sess, _, dt = run_session(
                [(0, Request(prompt=list(prompt), max_new=4, rid=0))],
                slots=2, sched_cfg={"chunk": c})
            rec = sess.records[0]
            pf[label] = {
                "first_token_calls":
                    rec["first_token_step"] - rec["admit_step"],
                "ttft_s": round(rec["first_token_time"]
                                - rec["submit_time"], 4)}
        out["prefill"] = pf
        # paged decode + chunked-prefill winners tuned by these sessions
        # (Engine.session pre-tunes both) — recorded like the FC tiles so
        # the serving perf trajectory names the kernels behind it
        snap = tune.snapshot()
        out["tiles"] = {k: v for k, v in snap.items()
                        if k not in seen_tiles}

        # --- heterogeneous continuous batching (best-of-3) -------------
        wl = schd.WorkloadSpec.preset(
            "heterogeneous", n_requests=10, vocab=cfg.vocab, seed=0)
        best = None
        for _ in range(3):
            sess, _, dt = run_session(schd.generate(wl),
                                      sched_cfg={"chunk": chunk})
            summ = schd.summarize(sess.records, dt, sess.stats["steps"])
            if best is None or summ["tok_per_s"] > best["tok_per_s"]:
                best = summ
        out["throughput"] = best

        # --- shared-prefix page reuse ----------------------------------
        wl = schd.WorkloadSpec.preset(
            "shared-prefix", n_requests=6, vocab=cfg.vocab, seed=1)
        sess, res, dt = run_session(
            schd.generate(wl),
            sched_cfg={"chunk": chunk, "prefix_cache": True})
        cache = sess.prefix
        out["prefix"] = {
            "requests": len(res),
            "page_hits": sess.stats["prefix_pages_reused"],
            "cache": cache.stats(),
            "pages_leaked": sess.alloc.in_use - cache.pages,
        }
        cache.clear(sess.alloc)
        out["prefix"]["pages_leaked_after_clear"] = sess.alloc.in_use

        # --- preemption under page pressure ----------------------------
        reqs = [(0, Request(prompt=[2 + i] * page_size, max_new=2 *
                            page_size, rid=i)) for i in range(6)]
        need = schd.scheduler.page_need(page_size, 2 * page_size,
                                        max_len, page_size)
        sess, res, dt = run_session(reqs, slots=3,
                                    pool=1 + 3 * need - 2,
                                    sched_cfg={"chunk": chunk})
        out["preemption"] = {
            "requests": len(reqs), "completed": len(res),
            "preemptions": sess.stats["preemptions"],
            "pages_leaked": sess.alloc.in_use,
        }
        return out

    def disagg_benchmark(self, mode: str = "aida", density: float = 0.25,
                         chunk: int = 8, page_size: int = 8,
                         max_len: int = 64, n_requests: int = 12) -> dict:
        """The `"disagg"` section of BENCH_api.json: disaggregated
        prefill/decode vs the co-located engine on the same ``burst``
        workload (the arrival pattern disaggregation exists for — a
        burst of prompts stalls a co-located batch's decoders).

        Deterministic facts carry the CI gate: token parity between the
        two engine shapes, handoff count == decode-bound requests, zero
        pages leaked on any allocator.  Wall-clock TTFT-p99 and tok/s
        are the dual-unit trajectory signal."""
        from repro import sched as schd
        cfg = self.cfg
        if cfg is None or not schd.supports_chunked_prefill(cfg):
            raise CapabilityError(
                "disagg_benchmark needs an arch whose per-request state "
                "is entirely KV pages (sched.supports_chunked_prefill)")
        eng = Engine(cfg, params=self.params)
        if mode != "dense":
            eng.compress(CompressionSpec(mode=mode, density=density),
                         verbose=None)
        wl = schd.WorkloadSpec.preset("burst", n_requests=n_requests,
                                      vocab=cfg.vocab, seed=0)
        arrivals = schd.generate(wl)

        def replay():
            return [(t, Request(prompt=list(r.prompt), max_new=r.max_new,
                                rid=r.rid)) for t, r in arrivals]

        # matched slot widths: the comparison isolates role separation
        # itself (decoders never occupying prompt-admission slots), not a
        # capacity difference
        sched_cfg = {"chunk": chunk}
        dcfg = {"prefill_slots": 4, "decode_slots": 4}
        out = {"mode": mode, "chunk": chunk, "workload": "burst",
               "requests": n_requests}
        # warm both engine shapes so TTFT measures scheduling, not XLA
        for dis in (None, dict(dcfg)):
            s = eng.session(max_len=max_len, kv_cache="paged",
                            page_size=page_size, scheduler=sched_cfg,
                            disagg=dis)
            s.submit(Request(prompt=[1] * (chunk + 1), max_new=2, rid=-1))
            s.run()
        for label, dis in (("colocated", None), ("disagg", dict(dcfg))):
            best = None
            for _ in range(3):
                sess = eng.session(batch_slots=4, max_len=max_len,
                                   kv_cache="paged", page_size=page_size,
                                   scheduler=sched_cfg, disagg=dis)
                t0 = time.perf_counter()
                res = sess.run_workload(replay())
                dt = time.perf_counter() - t0
                if dis is None:
                    summ = schd.summarize(sess.records, dt,
                                          sess.stats["steps"])
                    leaked = sess.alloc.in_use
                else:
                    summ = schd.summarize(
                        sess.records, dt,
                        sess.pre.stats["steps"] + sess.dec.stats["steps"],
                        roles=sess.role_stats())
                    leaked = sess.pre.alloc.in_use + sess.dec.alloc.in_use
                summ["pages_leaked"] = leaked
                summ["tokens_by_rid"] = {r.rid: r.tokens for r in res}
                if best is None or (summ["tok_per_s"] or 0) > \
                        (best["tok_per_s"] or 0):
                    best = summ
            out[label] = best
        out["token_parity"] = \
            out["colocated"].pop("tokens_by_rid") == \
            out["disagg"].pop("tokens_by_rid")
        return out

    def resil_benchmark(self, mode: str = "aida", density: float = 0.25,
                        chunk: int = 8, page_size: int = 8,
                        max_len: int = 64, n_requests: int = 8,
                        seed: int = 0) -> dict:
        """The `"resil"` section of BENCH_api.json: the burst workload
        through the disaggregated engine under every built-in FaultPlan
        preset, against a fault-free baseline.

        Deterministic facts carry the CI gate: every request completes,
        completed token streams are identical to the fault-free run,
        zero pages leak on either role's allocator, and the
        shed/retry/deadline-miss/fault counters are identical across two
        replays of the same ``(seed, preset)``.  The goodput ratio vs
        clean is the wall-clock trajectory signal."""
        from repro import sched as schd
        cfg = self.cfg
        if cfg is None or not schd.supports_chunked_prefill(cfg):
            raise CapabilityError(
                "resil_benchmark drives the disaggregated engine; it "
                "needs an arch whose per-request state is entirely KV "
                "pages (sched.supports_chunked_prefill)")
        eng = Engine(cfg, params=self.params)
        if mode != "dense":
            eng.compress(CompressionSpec(mode=mode, density=density),
                         verbose=None)
        wl = schd.WorkloadSpec.preset("burst", n_requests=n_requests,
                                      vocab=cfg.vocab, seed=0)
        arrivals = schd.generate(wl)

        def replay():
            return [(t, Request(prompt=list(r.prompt), max_new=r.max_new,
                                rid=r.rid)) for t, r in arrivals]

        sched_cfg = {"chunk": chunk}
        dcfg = {"prefill_slots": 2, "decode_slots": 4}

        def run(resil):
            sess = eng.session(max_len=max_len, kv_cache="paged",
                               page_size=page_size, scheduler=sched_cfg,
                               disagg=dict(dcfg), resil=resil)
            t0 = time.perf_counter()
            res = sess.run_workload(replay(), on_incomplete="warn")
            dt = time.perf_counter() - t0
            n_tok = sum(len(r.tokens) for r in res)
            counters = None
            if resil is not None:
                s = sess.resil_summary()
                counters = {k: s.get(k, 0) for k in
                            ("deadline_miss", "shed", "retries", "failed",
                             "fault_steps", "handoff_fallbacks")}
                counters["faults"] = s.get("faults", {})
            return {"tokens_by_rid": {r.rid: r.tokens for r in res},
                    "completed": len(res),
                    "failed": sorted(f.rid for f in sess.failed),
                    "tok_per_s": round(n_tok / dt, 2) if dt > 0 else None,
                    "pages_leaked": sess.pre.alloc.in_use
                    + sess.dec.alloc.in_use,
                    "counters": counters}

        # warm the compiled steps once so wall-clock ratios measure
        # scheduling under faults, not XLA compilation
        warm = eng.session(max_len=max_len, kv_cache="paged",
                           page_size=page_size, scheduler=sched_cfg,
                           disagg=dict(dcfg))
        warm.submit(Request(prompt=[1] * (chunk + 1), max_new=2, rid=-1))
        warm.run()
        clean = run(None)
        out = {"mode": mode, "workload": "burst", "requests": n_requests,
               "seed": seed,
               "clean": {"completed": clean["completed"],
                         "tok_per_s": clean["tok_per_s"],
                         "pages_leaked": clean["pages_leaked"]},
               "presets": {}}
        for preset in ("drop-handoff", "role-stall", "page-spike",
                       "straggler"):
            rcfg = {"fault_plan": f"{preset}:{seed}", "max_retries": 2,
                    "watchdog_every": 4}
            a = run(dict(rcfg))
            b = run(dict(rcfg))   # replay: counters must be identical
            parity = all(clean["tokens_by_rid"].get(rid) == toks
                         for rid, toks in a["tokens_by_rid"].items())
            out["presets"][preset] = {
                "completed": a["completed"],
                "failed": a["failed"],
                "token_parity": parity,
                "pages_leaked": a["pages_leaked"],
                "deterministic": (a["counters"] == b["counters"]
                                  and a["tokens_by_rid"]
                                  == b["tokens_by_rid"]),
                "counters": a["counters"],
                "goodput_vs_clean": (
                    round(a["tok_per_s"] / clean["tok_per_s"], 3)
                    if a["tok_per_s"] and clean["tok_per_s"] else None),
            }
        return out

    def capacity_benchmark(self, workload="burst", n_requests: int = 8,
                           sweep: Optional[Sequence[dict]] = None,
                           slo=None, page_size: int = 8,
                           max_len: int = 64, max_steps: int = 4000,
                           seed: int = 0) -> dict:
        """The `"capacity"` section of BENCH_api.json: trace-driven
        capacity planning (ROADMAP item 4's "how many AIDA-class devices
        serve N users at p99 < X?" in single-engine form).

        Replays one workload — a preset name or a ``WorkloadSpec``
        (e.g. ``WorkloadSpec.from_trace`` of a recorded serve) — across
        a sweep of ``(slots, kv_pool_pages, chunk, policy)`` configs,
        feeds each run's live trace through ``repro.obs.analyze``, and
        names the smallest config meeting the declared ``slo``
        (smallest = first in ascending (slots, kv_pool_pages, chunk,
        policy) order).

        Everything in the section is tick-denominated and therefore
        deterministic: no wall-clock numbers, and the chosen config is
        re-run once to assert its ``TraceReport`` replays
        byte-identically — both facts gate in CI
        (benchmarks/check_regression.py)."""
        import warnings

        from repro import sched as schd
        from repro.obs import Tracer
        from repro.obs.analyze import PHASES, SLOSpec, analyze
        if slo is None:
            slo = CAPACITY_SLO
        if isinstance(slo, str):
            slo = SLOSpec.parse(slo)
        if isinstance(workload, schd.WorkloadSpec):
            wl, wl_name = workload, \
                ("trace" if workload.schedule is not None else "spec")
        else:
            wl_name = workload
            wl = schd.WorkloadSpec.preset(
                workload, n_requests=n_requests,
                vocab=self.cfg.vocab if self.cfg else 256, seed=seed)
        arrivals = schd.generate(wl)
        if sweep is None:
            sweep = [dict(c) for c in CAPACITY_SMOKE_SWEEP]

        def norm(c: dict) -> dict:
            return {"slots": int(c.get("slots", 4)),
                    "kv_pool_pages": c.get("kv_pool_pages"),
                    "chunk": int(c.get("chunk", 8)),
                    "policy": c.get("policy", "fifo")}

        def key(c: dict):
            # "smallest config": fewest slots, then smallest pool
            # (None = the session default pool, largest), then chunk,
            # then policy name — a total deterministic order
            pool = c["kv_pool_pages"]
            return (c["slots"], pool if pool is not None else 10 ** 9,
                    c["chunk"], c["policy"])

        def label(c: dict) -> str:
            return (f"slots={c['slots']},pages={c['kv_pool_pages']},"
                    f"chunk={c['chunk']},policy={c['policy']}")

        def run(c: dict):
            tracer = Tracer()
            sess = self.session(
                batch_slots=c["slots"], max_len=max_len,
                kv_cache="paged", page_size=page_size,
                kv_pool_pages=c["kv_pool_pages"],
                scheduler={"chunk": c["chunk"], "policy": c["policy"]},
                obs=tracer)
            replay = [(t, Request(prompt=list(r.prompt),
                                  max_new=r.max_new, rid=r.rid))
                      for t, r in arrivals]
            with warnings.catch_warnings():
                # an under-provisioned sweep point SHOULD fail its SLO,
                # not crash or warn-spam: partial completion is data here
                warnings.simplefilter("ignore")
                sess.run_workload(replay, max_steps=max_steps,
                                  on_incomplete="warn")
            return analyze(tracer, slo=slo)

        configs = sorted((norm(c) for c in sweep), key=key)
        out = {"workload": wl_name, "requests": wl.n_requests,
               "seed": seed, "page_size": page_size,
               "slo": slo.describe(),
               "order": "ascending (slots, kv_pool_pages, chunk, policy)",
               "sweep": [], "chosen": None}
        reports = {}
        for c in configs:
            rep = run(c)
            lbl = label(c)
            reports[lbl] = (c, rep)
            n_req = len(rep.requests)
            completed = sum(1 for r in rep.requests.values()
                            if r["outcome"] == "completed")
            out["sweep"].append({
                "config": c, "label": lbl,
                "slo_pass": rep.slo["pass"],
                "metrics": rep.slo["metrics"],
                "requests": n_req, "completed": completed,
                "span_ticks": rep.ticks["span"],
                "critical_path_ticks": {
                    p: rep.critical_path[p]["ticks"] for p in PHASES},
                "segments_ok": rep.segments_consistent(),
            })
            if out["chosen"] is None and rep.slo["pass"]:
                out["chosen"] = lbl
        # replay gate: the named config's report must be a pure function
        # of the (workload, config) — rerun it and diff the bytes
        probe = out["chosen"] or (out["sweep"][0]["label"]
                                  if out["sweep"] else None)
        if probe is not None:
            c, rep = reports[probe]
            out["deterministic_replay"] = \
                run(c).to_json() == rep.to_json()
        else:
            out["deterministic_replay"] = False
        return out

    def benchmark(self, modes: Sequence[str] = ("dense", "aida"),
                  requests: int = 4, max_new: int = 8,
                  batch_slots: int = 2, density: float = 0.25,
                  problem: Optional[FCProblem] = None,
                  kv_mode: Optional[str] = "aida") -> dict:
        """Serve each mode through the facade and price the cost-model
        backends on one FC instance; returns a JSON-ready dict
        (benchmarks/run.py writes it to BENCH_api.json)."""
        from repro.kernels import tune
        from repro.obs import provenance
        out = {
            # run provenance rides at the top of every BENCH_api.json so
            # a regression report names the exact setup that produced it
            "provenance": provenance(
                config=getattr(self.cfg, "name", None),
                mode=",".join(modes), seed=self._seed,
                backend=self.backend.name),
            "backends": {}, "modes": {}}
        reqs = [Request(prompt=[1, 2 + i % 7, 3], max_new=max_new, rid=i)
                for i in range(requests)]
        # entries already in the process-global cache were tuned by earlier
        # sessions, not by this benchmark — attribute only new winners
        seen_tiles = set(tune.snapshot())
        for mode in modes:
            eng = Engine(self.cfg, params=self.params)
            if mode != "dense":
                eng.compress(CompressionSpec(mode=mode, density=density))
            sess = eng.session(batch_slots=batch_slots,
                               max_len=max_new + 8)
            sess.submit(Request(prompt=[1], max_new=1, rid=-1))
            sess.run()  # warm the compiled step
            sess.results.clear()
            # best-of-3 passes: a single load spike on a shared host can
            # halve one mode's tok/s and flake the CI gate.  (dt, n_tok)
            # travel as a pair — the fastest pass's own token count.
            dt, n_tok = float("inf"), 0
            for _ in range(3):
                for r in reqs:
                    sess.submit(r)
                t0 = time.perf_counter()
                res = sess.run()
                pass_dt = time.perf_counter() - t0
                pass_tok = sum(len(r.tokens) for r in res)
                sess.results.clear()
                if pass_tok / pass_dt > (n_tok / dt if n_tok else 0.0):
                    dt, n_tok = pass_dt, pass_tok
            # tiles the autotuner picked for this mode's layer shapes —
            # recorded so the perf trajectory is reproducible
            snap = tune.snapshot()
            tiles = {k: v for k, v in snap.items() if k not in seen_tiles}
            seen_tiles.update(snap)
            out["modes"][mode] = {
                "backend": eng.backend.name,
                "tokens": n_tok, "seconds": round(dt, 4),
                "tok_per_s": round(n_tok / dt, 2),
                "tiles": tiles,
                "compression_ratio": (round(eng.stats["ratio"], 2)
                                      if eng.stats else 1.0)}
        if kv_mode is not None and self.cfg.family != "rwkv6":
            # paged-vs-dense KV cache section (attention time share, KV
            # bytes/token, paged step-time parity) — gated by
            # benchmarks/check_regression.py alongside the FC modes;
            # attention-free archs have nothing to page
            out["kv"] = self.kv_benchmark(mode=kv_mode,
                                          batch_slots=batch_slots,
                                          density=density)
            # scheduler section: chunked-prefill TTFT, heterogeneous
            # continuous-batching throughput/latency, prefix-cache reuse,
            # preemption-instead-of-OutOfPages — also CI-gated
            out["serving"] = self.serving_benchmark(mode=kv_mode,
                                                    density=density)
            from repro import sched as schd
            if schd.supports_chunked_prefill(self.cfg):
                # disaggregated prefill/decode vs co-located on the burst
                # preset: token parity + handoff/migration accounting +
                # TTFT-p99 — also CI-gated
                out["disagg"] = self.disagg_benchmark(mode=kv_mode,
                                                      density=density)
                # resilience section: burst under every FaultPlan preset
                # — token parity vs clean, zero leaks, deterministic
                # counters — also CI-gated
                out["resil"] = self.resil_benchmark(mode=kv_mode,
                                                    density=density)
                # capacity section: the burst preset swept over
                # (slots, pool, chunk, policy), each run's trace fed
                # through obs.analyze, smallest SLO-meeting config named
                # — tick-denominated, fully deterministic, CI-gated.
                # Ticks depend only on scheduling, not kernels, so the
                # dense engine (self) is the cheap honest substrate.
                out["capacity"] = self.capacity_benchmark()
        if problem is None:
            rng = np.random.default_rng(0)
            w = rng.integers(-15, 16, size=(24, 32)) \
                * (rng.random((24, 32)) < 0.3)
            b = rng.integers(-15, 16, size=(32,)) * (rng.random(32) < 0.6)
            problem = FCProblem(w=w, b=b, m=4, n=4)
        emu = self.estimate(backend="ap-emulator", workload=problem)
        sim = self.estimate(backend="cycle-sim", workload=problem)
        alex = self.estimate(backend="cycle-sim", workload="alexnet-fc")
        eie = self.estimate(backend="cycle-sim", workload="alexnet-fc",
                            simulator="eie")
        out["backends"]["ap-emulator"] = {
            "fc_cycles": int(emu["cycles"]), "exact": emu["exact"]}
        out["backends"]["cycle-sim"] = {
            "fc_cycles": int(sim["cycles"]),
            "agrees_with_emulator": int(sim["cycles"]) == int(emu["cycles"]),
            "alexnet_fc_cycles": int(alex["cycles"]),
            "alexnet_fc_inf_per_s": round(alex["inf_per_s"], 1),
            "eie_alexnet_fc_cycles": int(eie["cycles"]),
            "eie_alexnet_fc_inf_per_s": round(eie["inf_per_s"], 1)}
        return out
