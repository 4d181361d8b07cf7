"""Benchmark runner — one section per paper table/figure + kernel accounting,
plus the unified-API backend benchmark (machine-readable BENCH_api.json).

  PYTHONPATH=src python -m benchmarks.run [--api-only] [--out PATH]
"""
from __future__ import annotations

import json
import sys
import time


def _out_path(default: str = "BENCH_api.json") -> str:
    if "--out" in sys.argv:
        i = sys.argv.index("--out") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            sys.exit("usage: benchmarks.run [--api-only] [--out PATH]")
        return sys.argv[i]
    return default


# The sharding section runs in a SUBPROCESS: the bench process must keep
# 1 device (dry-run isolation rule), and jax locks the device count on
# first backend init.  Parity is the deterministic CI assertion; the
# per-shard step time is the (host-noisy) trajectory, gated dual-unit
# like the FC modes (absolute OR mesh/single ratio, host speed cancels).
_SHARD_BENCH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
from repro.api import CompressionSpec, Engine, Request
from repro.configs import get, reduced
from repro.launch.mesh import make_host_mesh

cfg = reduced(get("llama3-8b"), n_layers=2, d_model=128, d_ff=256,
              vocab=512)
eng = Engine(cfg).compress(CompressionSpec(mode="aida", density=0.25,
                                           block_rows=32), verbose=None)
reqs = [Request(prompt=[1, 2 + i % 7, 3], max_new=8, rid=i)
        for i in range(4)]

def serve(mesh=None):
    sess = eng.session(batch_slots=2, max_len=32, mesh=mesh,
                       scheduler={"chunk": 4})
    sess.submit(Request(prompt=[1], max_new=1, rid=-1))
    sess.run()
    sess.results.clear()
    best_tps, best_step, toks = 0.0, float("inf"), None
    for _ in range(3):
        s0 = sess.stats["steps"]
        for r in reqs:
            sess.submit(r)
        t0 = time.perf_counter()
        res = sess.run()
        dt = time.perf_counter() - t0
        n = sum(len(r.tokens) for r in res)
        steps = sess.stats["steps"] - s0
        best_tps = max(best_tps, n / dt)
        best_step = min(best_step, dt / steps)
        toks = [r.tokens for r in res]
        sess.results.clear()
    return best_tps, best_step, toks

tps1, step1, ref = serve()
tpsN, stepN, got = serve(make_host_mesh(n_model=4, n_data=2))
from repro.kernels import tune
print(json.dumps({
    "mode": "aida", "n_model": 4, "n_data": 2,
    "token_parity": got == ref,
    "tok_per_s_single": round(tps1, 2),
    "tok_per_s_mesh": round(tpsN, 2),
    "mesh_over_single": round(tpsN / tps1, 4),
    "decode_step_us": round(stepN * 1e6, 1),
    "decode_step_us_per_shard": round(stepN * 1e6 / 4, 1),
    # paged decode/chunk winners the mesh session resolved at its GLOBAL
    # geometry keys (shard_map wrappers pass them into every shard)
    "paged_tiles": {k: v for k, v in tune.snapshot().items()
                    if k.startswith("paged-attn")},
}))
"""


def bench_sharding() -> dict:
    """Mesh-aware serving section: (model=4, data=2) host mesh vs single
    device on the aida mode — token parity (deterministic gate) +
    per-shard decode step time (trajectory)."""
    import json as _json
    import os
    import subprocess

    # the child is an emulated CPU mesh by design, so it never reaches
    # for an accelerator this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARD_BENCH], env=env,
                         capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"sharding bench failed:\n{out.stderr[-2000:]}")
    return _json.loads(out.stdout.strip().splitlines()[-1])


def bench_api(out_path: str = "BENCH_api.json") -> dict:
    """Serve + cost-model every backend through `repro.api.Engine` and
    write tokens/s + cycle counts to `out_path` so future PRs have a perf
    trajectory to compare against."""
    from repro.api import Engine
    from repro.configs import get, reduced

    cfg = reduced(get("llama3-8b"), n_layers=2, d_model=128, d_ff=256,
                  vocab=512)
    eng = Engine(cfg)
    # 8 requests x 16 tokens per mode: ~0.5s+ measured per mode, enough to
    # keep host scheduling noise inside the CI gate's 20% tolerance
    data = eng.benchmark(modes=("dense", "int8", "codebook4", "acsr",
                                "aida"),
                         requests=8, max_new=16, batch_slots=2)
    data["sharding"] = bench_sharding()
    data["meta"] = {"arch": cfg.name, "host": "cpu-interpret",
                    "note": "tok/s on host CPU interpret-mode kernels — "
                            "trajectory signal, not TPU perf"}
    with open(out_path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    for mode, rec in data["modes"].items():
        print(f"  {mode:10s} [{rec['backend']:9s}] {rec['tok_per_s']:8.1f} "
              f"tok/s  ratio {rec['compression_ratio']:.2f}x")
    kv = data.get("kv")
    if kv:
        share = kv["attn_time_share"]
        bpt = kv["kv_bytes_per_token"]
        print(f"  kv[{kv['mode']}]    full {kv['full']['tok_per_s']:.1f} "
              f"tok/s vs paged {kv['paged']['tok_per_s']:.1f} "
              f"(x{kv['paged_over_full']:.2f}); attn share "
              f"full {share['full']:.0%} / paged {share['paged']:.0%}; "
              f"KV {bpt['paged_int8']:.0f} vs {bpt['dense_bf16']:.0f} "
              f"B/token ({bpt['ratio']:.2f}x)")
    sv = data.get("serving")
    if sv:
        pf, th = sv["prefill"], sv["throughput"]
        print(f"  serving[{sv['mode']}] prefill {pf['prompt_len']} toks: "
              f"{pf['chunked']['first_token_calls']} calls (chunk "
              f"{sv['chunk']}) vs {pf['one_token']['first_token_calls']} "
              f"one-token (bound {pf['bound_calls']}); "
              f"hetero {th['tok_per_s']:.1f} tok/s, "
              f"goodput {th['goodput_req_per_s']:.2f} req/s, "
              f"TTFT p50 {th['ttft_s']['p50']*1e3:.0f} ms")
        print(f"  serving prefix-cache: {sv['prefix']['page_hits']} page "
              f"hits / {sv['prefix']['cache']['inserted']} cached; "
              f"preemption: {sv['preemption']['preemptions']} evictions, "
              f"{sv['preemption']['completed']}/"
              f"{sv['preemption']['requests']} completed, "
              f"{sv['preemption']['pages_leaked']} pages leaked")
    rs = data.get("resil")
    if rs:
        worst = None
        for preset, rec in sorted(rs["presets"].items()):
            g = rec.get("goodput_vs_clean")
            if g is not None and (worst is None or g < worst[1]):
                worst = (preset, g)
        all_ok = all(rec["token_parity"] and rec["pages_leaked"] == 0
                     and rec["deterministic"]
                     for rec in rs["presets"].values())
        print(f"  resil[{rs['mode']}]   {len(rs['presets'])} fault presets"
              f" x {rs['clean']['completed']} requests: "
              f"{'parity OK, 0 leaks, deterministic' if all_ok else 'FAIL'}"
              + (f"; worst goodput {worst[1]:.2f}x clean ({worst[0]})"
                 if worst else ""))
    cap = data.get("capacity")
    if cap:
        n_pass = sum(1 for e in cap["sweep"] if e["slo_pass"])
        print(f"  capacity[{cap['workload']}] {len(cap['sweep'])} configs"
              f" x {cap['requests']} requests: {n_pass} meet SLO "
              f"{cap['slo']}; chosen {cap['chosen']}; "
              f"replay deterministic {cap['deterministic_replay']}")
    sh = data.get("sharding")
    if sh:
        print(f"  sharding[{sh['mode']}] mesh {sh['n_model']}x"
              f"{sh['n_data']} (model x data): parity "
              f"{'OK' if sh['token_parity'] else 'LOST'}; "
              f"{sh['tok_per_s_mesh']:.1f} tok/s sharded vs "
              f"{sh['tok_per_s_single']:.1f} single "
              f"(x{sh['mesh_over_single']:.2f}); decode step "
              f"{sh['decode_step_us_per_shard']:.0f} us/shard")
        for key, ch in sorted(sh.get("paged_tiles", {}).items()):
            tiles = {k: v for k, v in ch.items() if k not in ("impl", "us")}
            print(f"    paged tile {key}: {ch['impl']} {tiles} "
                  f"({ch.get('us', float('nan')):.0f} us)")
    sim = data["backends"]["cycle-sim"]
    print(f"  ap-emulator FC cycles: "
          f"{data['backends']['ap-emulator']['fc_cycles']}  "
          f"cycle-sim: {sim['fc_cycles']} "
          f"(agree: {sim['agrees_with_emulator']})")
    print(f"  AlexNet-FC cycle-sim: AIDA {sim['alexnet_fc_cycles']} cyc "
          f"({sim['alexnet_fc_inf_per_s']:.0f} inf/s) vs "
          f"EIE {sim['eie_alexnet_fc_cycles']} cyc "
          f"({sim['eie_alexnet_fc_inf_per_s']:.0f} inf/s)")
    print(f"  -> wrote {out_path}")
    return data


def main() -> int:
    t0 = time.time()
    if "--api-only" in sys.argv:
        print("=" * 72)
        print("API — unified facade backend benchmark (repro.api.Engine)")
        print("=" * 72)
        bench_api(out_path=_out_path())
        print(f"\n[benchmarks] done in {time.time()-t0:.0f}s")
        return 0
    from benchmarks import fig5, kernels_bench, table1

    print("=" * 72)
    print("TABLE 1 — AIDA vs EIE (calibrated analytical simulators)")
    print("=" * 72)
    table1.run()
    ok = table1.validate()
    print(f"\n  -> paper-claim validation (PP 14.5x, thrpt 2.5x, EE, power): "
          f"{'PASS' if ok else 'FAIL'}")

    print()
    print("=" * 72)
    print("FIG 5(a) — area / energy efficiency vs weight sparsity")
    print("=" * 72)
    rows = fig5.sparsity_sweep()
    lin = all(r2["rel_area"] > r1["rel_area"]
              for r1, r2 in zip(rows, rows[1:]))
    print(f"  -> area grows monotonically with density (linear-in-sparsity "
          f"claim): {'PASS' if lin else 'FAIL'}")

    print()
    print("=" * 72)
    print("FIG 5(b) — area / energy efficiency vs wordlength")
    print("=" * 72)
    rows = fig5.precision_sweep()
    mono = all(r1["rel_ee"] >= r2["rel_ee"] for r1, r2
               in zip(rows, rows[1:]))
    quad = rows[-1]["mult_cycles"] / rows[2]["mult_cycles"] > 8  # 16b vs 4b
    print(f"  -> EE best at binary/ternary and monotone in wordlength: "
          f"{'PASS' if mono else 'FAIL'}; multiply-stage cycles quadratic "
          f"(16b/4b > 8x): {'PASS' if quad else 'FAIL'}\n"
          f"     (note: END-TO-END EE gain is sub-quadratic because the "
          f"soft reduction, not the multiply, dominates at short "
          f"wordlengths — see EXPERIMENTS.md)")

    print()
    print("=" * 72)
    print("§4.3 — broadcast/M×V overlap scalability")
    print("=" * 72)
    ov = fig5.overlap_scalability()
    ov_ok = 1.3 < ov["best_speedup"] <= 2.0 and 0.2 < ov["area_overhead"] < 0.6
    print(f"  -> 'up to 1.86x at +28% area': "
          f"{'PASS' if ov_ok else 'FAIL'} "
          f"(model: {ov['best_speedup']:.2f}x, +{ov['area_overhead']:.0%})")

    print()
    print("=" * 72)
    print("KERNELS — compression dividend (HBM bytes) + host wall-clock")
    print("=" * 72)
    kernels_bench.bytes_model()
    print("\nwall-clock (host CPU, interpret-mode kernels — correctness "
          "path, not TPU perf):")
    kernels_bench.wallclock()
    kernels_bench.attention_bench()

    print()
    print("=" * 72)
    print("API — unified facade backend benchmark (repro.api.Engine)")
    print("=" * 72)
    bench_api(out_path=_out_path())

    print(f"\n[benchmarks] done in {time.time()-t0:.0f}s")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
