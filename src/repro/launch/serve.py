"""Serving launcher: batched decode through the `repro.api.Engine` facade,
optionally AIDA-compressed weights, with reproducible heterogeneous
workloads driven by `repro.sched.workload`.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --compress aida --density 0.1 --requests 16 \
      --workload heterogeneous --chunk 8 --policy sjf
(Full-size archs need a checkpoint; without one this initializes random
weights at a REDUCED size for a functional smoke serve.)

Mesh serving (tensor-parallel over an explicit ShardingPlan): ``--mesh
MODELxDATA`` (e.g. ``--mesh 4x2``) builds a host mesh through
`launch.mesh.make_host_mesh`; on a laptop/CI host export
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first.

Disaggregated serving (repro.disagg): ``--disagg`` splits the engine
into a prefill role and a decode role with KV page migration between
their pools; ``--prefill-devices N --decode-devices M`` additionally
puts the roles on disjoint device subsets (each role needs >= 1
device — the launcher force-emulates N+M host devices when XLA_FLAGS
is not already set).
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _parse_mesh(arg: str):
    """"4" -> model=4; "4x2" -> model=4, data=2."""
    parts = arg.lower().split("x")
    try:
        n_model = int(parts[0])
        n_data = int(parts[1]) if len(parts) > 1 else None
    except ValueError:
        sys.exit(f"--mesh wants MODEL or MODELxDATA, got {arg!r}")
    return n_model, n_data


def _early_arg(name: str):
    """Scan argv for ``--name VALUE`` / ``--name=VALUE`` BEFORE argparse
    runs — mesh/device degrees must be known before jax locks the
    process's device count on import."""
    for i, arg in enumerate(sys.argv):
        if arg == name and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
        if arg.startswith(name + "="):
            return arg.split("=", 1)[1]
    return None


# argv scan + XLA_FLAGS mutation ONLY when run as a program (python -m
# repro.launch.serve): importing this module must never read argv, call
# sys.exit, or change the process's jax device count.
if __name__ == "__main__":
    _mesh_arg = _early_arg("--mesh")
    if _mesh_arg is not None and "XLA_FLAGS" not in os.environ:
        n_model, n_data = _parse_mesh(_mesh_arg)
        n_dev = n_model * (n_data or 1)
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n_dev}"
    _pre_arg = _early_arg("--prefill-devices")
    _dec_arg = _early_arg("--decode-devices")
    if _pre_arg is not None and _dec_arg is not None \
            and "XLA_FLAGS" not in os.environ:
        try:
            _n_role = int(_pre_arg) + int(_dec_arg)
        except ValueError:
            _n_role = 0            # argparse will reject it properly
        if _n_role > 0:
            os.environ["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={_n_role}"

from repro.api import CompressionSpec, Engine, FaultPlan
from repro.configs import get, reduced
from repro.launch.mesh import make_host_mesh
from repro.resil import PRESETS as RESIL_PRESETS
from repro.sched import SchedConfig, WorkloadSpec, generate, summarize
from repro.sched.workload import PRESETS


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--compress", default=None,
                    choices=[None, "int8", "codebook4", "acsr", "aida"])
    ap.add_argument("--density", type=float, default=0.1)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--kv-cache", default=None,
                    choices=[None, "auto", "full", "paged"],
                    help="None/auto = paged page-pool KV wherever the "
                         "arch has attention (repro.kvstore)")
    ap.add_argument("--workload", default="uniform", choices=list(PRESETS),
                    help="request-mix preset (sched.workload): prompt "
                         "lengths, max_new, arrival process")
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="override the preset's prompt-length range with "
                         "a fixed length")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload RNG seed (schedules replay exactly)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill tokens per model call (1 = token-by-"
                         "token; paged KV + attention-only archs)")
    ap.add_argument("--policy", default="fifo", choices=["fifo", "sjf"],
                    help="admission order: FIFO or shortest-prompt-first")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share full prompt-prefix pages across requests")
    ap.add_argument("--kv-pool-pages", type=int, default=None,
                    help="page-pool size (small pools exercise admission "
                         "control + preemption instead of crashing)")
    ap.add_argument("--mesh", default=None,
                    help="tensor-parallel serving mesh, MODEL or "
                         "MODELxDATA (e.g. 4x2); sized via "
                         "launch.mesh.make_host_mesh")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: a prefill role and a "
                         "decode role with KV page migration "
                         "(repro.disagg)")
    ap.add_argument("--prefill-slots", type=int, default=4,
                    help="prefill-role batch slots (with --disagg)")
    ap.add_argument("--decode-slots", type=int, default=4,
                    help="decode-role batch slots (with --disagg)")
    ap.add_argument("--prefill-devices", type=int, default=None,
                    help="devices for the prefill role's mesh (with "
                         "--disagg; requires --decode-devices)")
    ap.add_argument("--decode-devices", type=int, default=None,
                    help="devices for the decode role's mesh (with "
                         "--disagg; requires --prefill-devices)")
    ap.add_argument("--fault-plan", default=None, metavar="PRESET:SEED",
                    help="inject deterministic faults (repro.resil): "
                         "one of " + ", ".join(
                             sorted(k for k in RESIL_PRESETS if k != "none"))
                         + "; e.g. drop-handoff:3")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="per-request completion budget in scheduler "
                         "ticks; missed deadlines become structured "
                         "RequestFailed results, not hangs")
    ap.add_argument("--max-retries", type=int, default=None,
                    help="recompute re-admissions allowed per request "
                         "before it fails with 'retries_exhausted' "
                         "(default 2 when the resil layer is on)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON "
                         "timeline of every serving seam (repro.obs); "
                         "tick-clock timestamps, so two same-seed runs "
                         "produce byte-identical traces")
    ap.add_argument("--trace-ring", type=int, default=None, metavar="N",
                    help="keep the last N events in a flight-recorder "
                         "ring; dumped to disk automatically on a "
                         "terminal HealthError/OutOfPages/RequestFailed")
    ap.add_argument("--profile-dir", default=None, metavar="PATH",
                    help="wrap the serve in a jax.profiler trace "
                         "(TensorBoard-loadable device profile)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the run's metrics + resil + role summary "
                         "as machine-readable JSON (with provenance)")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="write a repro.obs.analyze TraceReport of this "
                         "serve: per-request critical paths, queueing "
                         "split, per-role utilization, page pressure; "
                         "tick-denominated, so two same-seed runs "
                         "produce byte-identical reports")
    ap.add_argument("--slo", default=None, metavar="SPEC",
                    help="evaluate the run against an SLO, e.g. "
                         "'ttft_p99=40,tpot_p99=4,goodput=0.95' "
                         "(scheduler-tick units); verdict is printed "
                         "and embedded in --report")
    args = ap.parse_args()

    slo = None
    if args.slo is not None:
        from repro.obs import SLOSpec
        try:
            slo = SLOSpec.parse(args.slo)
        except ValueError as e:
            ap.error(str(e))

    resil = None
    if (args.fault_plan is not None or args.deadline_ticks is not None
            or args.max_retries is not None):
        if args.deadline_ticks is not None and args.deadline_ticks < 1:
            ap.error("--deadline-ticks must be >= 1")
        if args.max_retries is not None and args.max_retries < 0:
            ap.error("--max-retries must be >= 0")
        resil = {"watchdog_every": 8}
        if args.fault_plan is not None:
            try:
                resil["fault_plan"] = FaultPlan.parse(args.fault_plan)
            except ValueError as e:
                ap.error(str(e))
        if args.deadline_ticks is not None:
            resil["deadline_ticks"] = args.deadline_ticks
        if args.max_retries is not None:
            resil["max_retries"] = args.max_retries

    if (args.prefill_devices is not None) != (args.decode_devices is not None):
        ap.error("--prefill-devices and --decode-devices go together")
    if args.prefill_devices is not None:
        if not args.disagg:
            ap.error("--prefill-devices/--decode-devices need --disagg")
        if args.prefill_devices < 1 or args.decode_devices < 1:
            ap.error("each disaggregated role needs at least one device "
                     f"(got prefill={args.prefill_devices}, "
                     f"decode={args.decode_devices})")
    if args.disagg and args.mesh is not None:
        ap.error("--mesh and --disagg are mutually exclusive; give the "
                 "roles devices via --prefill-devices/--decode-devices")

    mesh = None
    if args.mesh is not None:
        n_model, n_data = _parse_mesh(args.mesh)
        mesh = make_host_mesh(n_model=n_model, n_data=n_data)
        print(f"[serve] mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    cfg = get(args.arch) if args.full_size else reduced(get(args.arch))
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no serving")
    print(f"[serve] {cfg.name}: ~{cfg.params_count()/1e6:.1f}M params")
    eng = Engine(cfg)
    if args.compress:
        eng.compress(CompressionSpec(mode=args.compress,
                                     density=args.density))
        print(f"[serve] {args.compress}: {eng.stats['n_compressed']} "
              f"projections, {eng.stats['ratio']:.1f}x weight memory "
              f"(backend: {eng.backend.name})")

    overrides = dict(n_requests=args.requests, max_new=(1, args.max_new),
                     vocab=cfg.vocab, seed=args.seed)
    if args.prompt_len is not None:
        overrides["prompt_len"] = (args.prompt_len, args.prompt_len)
    spec = WorkloadSpec.preset(args.workload, **overrides)
    arrivals = generate(spec)
    max_len = 128

    disagg = None
    if args.disagg:
        disagg = {"prefill_slots": args.prefill_slots,
                  "decode_slots": args.decode_slots,
                  "prefill_devices": args.prefill_devices,
                  "decode_devices": args.decode_devices}
    tracer = None
    # trace analysis (--report / --slo) runs over the same tick-clock
    # event stream the --trace export writes, so any of the four flags
    # turns the tracer on; capture stays off for a pure flight-recorder
    # ring (--trace-ring alone), which only needs the bounded buffer
    need_capture = (args.trace is not None or args.report is not None
                    or slo is not None)
    if need_capture or args.trace_ring is not None:
        from repro.obs import FlightRecorder, Tracer
        recorder = None
        if args.trace_ring is not None:
            if args.trace_ring < 1:
                ap.error("--trace-ring must be >= 1")
            # dump destination, most explicit wins: --profile-dir (the
            # run's artifact dir) > the --trace file's dir > cwd
            if args.profile_dir is not None:
                out_dir = args.profile_dir
                os.makedirs(out_dir, exist_ok=True)
            elif args.trace is not None:
                out_dir = os.path.dirname(os.path.abspath(args.trace))
            else:
                out_dir = "."
            recorder = FlightRecorder(capacity=args.trace_ring,
                                      out_dir=out_dir)
        tracer = Tracer(capture=need_capture, recorder=recorder)
    sess = eng.session(batch_slots=args.slots, max_len=max_len,
                       kv_cache=args.kv_cache,
                       kv_pool_pages=args.kv_pool_pages,
                       scheduler=SchedConfig(
                           policy=args.policy, chunk=args.chunk,
                           prefix_cache=args.prefix_cache),
                       mesh=mesh, disagg=disagg, resil=resil,
                       obs=tracer)
    pre = sess.pre if args.disagg else sess
    print(f"[serve] workload={args.workload} seed={args.seed} "
          f"kv={pre.kv_cache} chunk={pre.chunk} policy={args.policy}"
          + (" disagg" if args.disagg else ""))
    if resil is not None:
        print(f"[serve] resil: fault_plan="
              f"{args.fault_plan or 'none'} "
              f"deadline_ticks={args.deadline_ticks} "
              f"max_retries={resil.get('max_retries', 2)}")
    from repro.obs import profile_trace
    t0 = time.perf_counter()
    # injected faults / deadlines make partial completion an expected
    # outcome — report it instead of raising
    with profile_trace(args.profile_dir):
        results = sess.run_workload(
            arrivals, on_incomplete="warn" if resil is not None else "raise")
    dt = time.perf_counter() - t0
    rsumm = sess.resil_summary() if resil is not None else None
    if args.disagg:
        steps = sess.pre.stats["steps"] + sess.dec.stats["steps"]
        m = summarize(sess.records, dt, steps, roles=sess.role_stats(),
                      resil=rsumm)
    else:
        m = summarize(sess.records, dt, sess.stats["steps"], resil=rsumm)
    print(f"[serve] {m['completed']}/{m['requests']} requests, "
          f"{m['tokens']} tokens, {m['tok_per_s']:.1f} tok/s, "
          f"goodput {m['goodput_req_per_s']:.2f} req/s "
          f"({m['steps']} model calls)")
    if rsumm is not None:
        n_failed = len(sess.failed)
        line = (f"[serve] resil: shed {rsumm['shed']}, retries "
                f"{rsumm['retries']}, deadline misses "
                f"{rsumm['deadline_miss']}, failed {n_failed}")
        if rsumm.get("faults"):
            line += ", injected " + ", ".join(
                f"{k}={v}" for k, v in sorted(rsumm["faults"].items()))
        print(line)
        for f in sess.failed:
            print(f"[serve]   {f!r}")
    if m["ttft_s"]:
        print(f"[serve] TTFT p50 {m['ttft_s']['p50']*1e3:.0f} ms / "
              f"p99 {m['ttft_s']['p99']*1e3:.0f} ms; "
              f"preemptions {m['preemptions']}, "
              f"prefix pages reused {m['prefix_pages_reused']}")
    if args.disagg:
        roles, hand = m["roles"], m.get("handoff")
        line = (f"[serve] roles: prefill {roles['prefill']['steps']} "
                f"steps ({roles['prefill']['utilization'] or 0:.0%} busy),"
                f" decode {roles['decode']['steps']} steps "
                f"({roles['decode']['utilization'] or 0:.0%} busy)")
        if hand:
            line += (f"; handoffs {hand['count']}, mean latency "
                     f"{hand['latency_s']['mean']*1e3:.1f} ms, "
                     f"{hand['migrated_bytes']} bytes migrated")
        print(line)
        print(f"[serve] pages: prefill peak {sess.pre.stats['pages_peak']}"
              f" / decode peak {sess.dec.stats['pages_peak']}, "
              f"leaked {sess.pre.alloc.in_use + sess.dec.alloc.in_use}")
    elif sess.kv_cache == "paged":
        print(f"[serve] pages: peak {sess.stats['pages_peak']}, "
              f"allocs {sess.stats['page_allocs']}, "
              f"reclaimed(SWA) {sess.stats['pages_reclaimed_swa']}")
    if args.trace is not None:
        tracer.export(args.trace)
        wall = tracer.wall.summary()
        line = f"[serve] trace: {len(tracer.events)} events -> {args.trace}"
        if wall:
            line += "; wall " + ", ".join(
                f"{k} {v['seconds']:.2f}s/{v['calls']}" for k, v
                in wall.items())
        print(line)
    if args.report is not None or slo is not None:
        from repro.obs import analyze
        rep = analyze(tracer, slo=slo)
        shares = ", ".join(
            f"{ph} {rec['share']:.0%}" for ph, rec
            in rep.critical_path.items() if rec["ticks"])
        print(f"[serve] critical path ({rep.ticks['span']} ticks): "
              + (shares or "idle"))
        if not rep.segments_consistent():
            print("[serve] WARNING: per-request segments do not sum to "
                  "request spans — trace is incomplete or corrupt")
        if rep.slo is not None:
            verdict = "PASS" if rep.slo["pass"] else "FAIL"
            print(f"[serve] slo {verdict}: " + ", ".join(
                f"{name} {rec['value']} vs {rec['bound']} "
                f"({'ok' if rec['pass'] else 'VIOLATED'})"
                for name, rec in sorted(rep.slo["metrics"].items())))
            for name, rec in sorted(rep.slo["metrics"].items()):
                if rec["violators"]:
                    print(f"[serve]   {name} violators: rids "
                          f"{rec['violators']}")
        if args.report is not None:
            rep.write(args.report)
            print(f"[serve] report: trace analysis -> {args.report}")
    if args.profile_dir is not None:
        print(f"[serve] profile: jax trace -> {args.profile_dir}")
    if args.json is not None:
        import json

        from repro.obs import provenance
        if args.disagg:
            pages = {"prefill_peak": sess.pre.stats["pages_peak"],
                     "decode_peak": sess.dec.stats["pages_peak"],
                     "leaked": sess.pre.alloc.in_use
                     + sess.dec.alloc.in_use}
        elif sess.kv_cache == "paged":
            pages = {"peak": sess.stats["pages_peak"],
                     "allocs": sess.stats["page_allocs"],
                     "leaked": sess.alloc.in_use}
        else:
            pages = None
        dump = {
            "provenance": provenance(
                config=cfg.name, mode=args.compress or "dense",
                seed=args.seed, backend=eng.backend.name,
                workload=args.workload, disagg=bool(args.disagg)),
            "metrics": m,
            "failed": [{"rid": f.rid, "reason": f.reason,
                        "retries": f.retries}
                       for f in (sess.failed if resil is not None else [])],
            "pages": pages,
        }
        if tracer is not None:
            dump["wall_phases"] = tracer.wall.summary()
        with open(args.json, "w") as f:
            json.dump(dump, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[serve] json: metrics -> {args.json}")


if __name__ == "__main__":
    main()
