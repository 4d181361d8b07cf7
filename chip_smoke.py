#!/usr/bin/env python3
"""Serve qwen1.5-0.5b once on a TPU and check what comes out.

    python3 chip_smoke.py [--seed N]            # one chip
    python3 chip_smoke.py --four-chips [--seed N]

The model runs at its published widths (24 layers, d_model 1024, 16
heads of 64, d_ff 2816, 151,936-token vocabulary) with random weights
from ``--seed``, through the path a user calls: ``Engine`` ->
``compress`` -> ``Engine.session`` -> scheduler -> paged KV -> the
Pallas kernels.  Everything runs in this one process, which holds the
chip(s).

One chip, four phases:

* ``dense``   - dense weights, paged bf16 KV, chunked prefill, checked
  against the same requests on the full KV cache;
* ``int8_kv`` - the same requests on int8 KV pages;
* ``aida``    - ``CompressionSpec(mode="aida", density=0.25)`` on the
  ``pallas`` backend, served the same way;
* ``kernels`` - every serving Pallas kernel at this model's widths
  against its XLA/jnp reference, within written tolerances.

``--four-chips`` runs only the tensor-parallel path: ``dense`` and
``aida`` served on a 4-way ``model`` mesh, checked against the same
requests on one device of this process.

Two such runs compute the same function in different XLA programs, which
round differently on a TPU (see ``TOL_LOGITS``).  The second run is
teacher-forced onto the first run's greedy tokens, so both decode the
same token stream: every emitted token's logits must agree within a
written tolerance, and the two runs' greedy choices may differ only at
near-ties, where rounding can flip them.

Each phase prints one JSON line: set-up and compile seconds, the tokens
and wall seconds of the serve (a first reading, not a benchmark), the
device's peak memory, and the kernel tuner's choices with every
candidate it refused.  The last line is ``{"ok": true, "device": ...}``
and is printed only when every phase passed.  Without a TPU, or away
from the repo's sources, the script exits non-zero and prints no result.
"""
import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
N_REQUESTS, MAX_NEW = 8, 32
PROMPT_LEN = (64, 512)
SLOTS, MAX_LEN, CHUNK, PAGE = 4, 1024, 16, 16
FIRST_READING = "first chip reading, not a benchmark"


class SmokeFailure(Exception):
    """A check of the smoke run did not hold; ``report`` is what the
    phase had measured by then."""

    def __init__(self, msg: str, report: dict):
        super().__init__(msg)
        self.report = report


def check(cond, msg: str, report: dict) -> None:
    if not cond:
        raise SmokeFailure(msg, report)


def emit(record: dict) -> None:
    print(json.dumps(record, default=str), flush=True)


# ------------------------------------------------------------- serving
class LogitWatch:
    """Wraps a session's sampler and keeps a copy of every emitted
    token's logits, per request.

    With ``force`` ({rid: tokens} of a reference run) the sampler is
    handed a one-hot row on the reference's token in place of the
    logits, so this run decodes the reference's token stream (teacher
    forcing) and every step's logits can be compared with the
    reference's.  The timed serve pays one logits copy per token, and
    the one-hot row when forced; all checks run after it."""

    def __init__(self, sess, force=None):
        self.logits = {}
        sample = sess._emit

        def watched(i, logits_i, now):
            rid = sess.slot_entry[i].req.rid
            seen = self.logits.setdefault(rid, [])
            seen.append(np.array(logits_i, np.float32))
            if force is not None and rid in force:
                logits_i = np.zeros_like(seen[-1])
                logits_i[force[rid][len(seen) - 1]] = 1.0
            sample(i, logits_i, now)
        sess._emit = watched

    def nonfinite(self) -> int:
        return sum(not np.isfinite(row).all()
                   for rows in self.logits.values() for row in rows)


def margin(row: np.ndarray) -> float:
    """Top-1 minus top-2 logit: how close a greedy token was to flipping."""
    top2 = np.partition(row, -2)[-2:]
    return float(top2[1] - top2[0])


def make_requests(vocab: int, seed: int):
    from repro.api import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_REQUESTS)
    return [Request(prompt=rng.integers(0, vocab, n).tolist(),
                    max_new=MAX_NEW, rid=rid)
            for rid, n in enumerate(lens)]


def peak_bytes() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def serve(eng, requests, force=None, **session_kw):
    """Serve ``requests`` on a fresh session of ``eng``, teacher-forced
    onto ``force`` if given (see :class:`LogitWatch`).  Returns
    ({rid: tokens}, LogitWatch, report); checks completion, leaks, finite
    logits and that every tuned kernel lowered natively."""
    from repro.api import Request
    from repro.kernels import tune
    tuned_before = set(tune.snapshot())
    t0 = time.perf_counter()
    sess = eng.session(batch_slots=SLOTS, max_len=MAX_LEN, page_size=PAGE,
                       scheduler={"chunk": CHUNK}, **session_kw)
    setup_s = time.perf_counter() - t0
    watch = LogitWatch(sess, force)
    # one short request compiles the prefill and decode steps
    t0 = time.perf_counter()
    sess.submit(Request(prompt=list(range(1, CHUNK + 2)), max_new=2,
                        rid=-1))
    sess.run()
    compile_s = time.perf_counter() - t0
    for req in requests:
        sess.submit(dataclasses.replace(req))
    t0 = time.perf_counter()
    results = sess.run()
    wall_s = time.perf_counter() - t0
    tokens = {r.rid: r.tokens for r in results if r.rid >= 0}
    n_tokens = sum(len(t) for t in tokens.values())
    leaked = sess.alloc.in_use if sess.alloc is not None else 0
    tuned = {k: v for k, v in tune.snapshot().items()
             if k not in tuned_before}
    report = {"kv_cache": sess.kv_cache, "kv_dtype": sess.kv_dtype,
              "chunk": sess.chunk, "setup_s": setup_s,
              "compile_s": compile_s,
              FIRST_READING: {"tokens": n_tokens, "wall_s": wall_s,
                              "tok_per_s": n_tokens / wall_s},
              "completed": len(tokens), "pages_leaked": leaked,
              "nonfinite_logits": watch.nonfinite(),
              "peak_bytes_in_use": peak_bytes(), "tuned": tuned}
    check(sorted(tokens) == [r.rid for r in requests],
          f"requests completed: {sorted(tokens)}", report)
    check(all(len(t) == MAX_NEW for t in tokens.values()),
          "a request stopped short of max_new", report)
    check(leaked == 0, f"{leaked} pages leaked", report)
    check(report["nonfinite_logits"] == 0, f"{report['nonfinite_logits']} "
          "tokens had non-finite logits", report)
    check(all(k.endswith("/tpu") for k in tuned),
          f"tuner keys not lowered natively: "
          f"{[k for k in tuned if not k.endswith('/tpu')]}", report)
    return tokens, watch, report


#: Tolerances between two runs of the same token stream in different XLA
#: programs: paged vs full cache, 16-token prefill chunks vs one-token
#: steps, a 4-way mesh vs one device.  They round differently on a TPU:
#: on a v5e at this width their first-token logits differed by up to 0.1
#: (0.018 of the largest).  TOL_LOGITS bounds max |diff| / max |logit| of
#: every emitted token's logits; where the two runs' greedy tokens differ,
#: both top-1 margins must be below TIE_MARGIN logits, twice that
#: difference: a margin a rounding difference can overturn.
TOL_LOGITS, TIE_MARGIN = 0.05, 0.2


def agree(report: dict, name: str, ref: dict, w_ref: LogitWatch,
          w_forced: LogitWatch) -> None:
    """Record in ``report[name]`` how a run teacher-forced onto the greedy
    tokens ``ref`` differs from the run that chose them, and check that
    they agree up to rounding at every step: the largest relative logits
    error per token index, and each token where the forced run's own
    greedy choice flipped, with both runs' top-1 margins there."""
    step_err = [0.0] * MAX_NEW
    flips = []
    for rid, tokens in sorted(ref.items()):
        pairs = zip(w_ref.logits[rid], w_forced.logits[rid])
        for j, (a, b) in enumerate(pairs):
            err = float(np.abs(b - a).max() / np.abs(a).max())
            step_err[j] = max(step_err[j], err)
            if int(b.argmax()) != tokens[j]:
                flips.append({"rid": rid, "token": j,
                              "margins": [margin(a), margin(b)]})
    report[name] = {"step_rel_err_max": step_err, "tol": TOL_LOGITS,
                    "flips": flips}
    worst = max(step_err)
    check(worst <= TOL_LOGITS, f"{name}: logits differ by {worst:.3g} of "
          f"the largest (> {TOL_LOGITS})", report)
    wide = [f for f in flips if max(f["margins"]) > TIE_MARGIN]
    check(not wide, f"{name}: greedy tokens flip away from a near-tie: "
          f"{wide}", report)


def phase_dense(eng, requests):
    paged, w_paged, report = serve(eng, requests)
    check(report["kv_cache"] == "paged" and report["kv_dtype"] == "bf16",
          "the default session is not paged bf16", report)
    _, w_full, report["full_cache"] = serve(eng, requests, force=paged,
                                            kv_cache="full")
    agree(report, "paged_vs_full", paged, w_paged, w_full)
    return report


def phase_int8_kv(eng, requests):
    return serve(eng, requests, kv_dtype="int8")[2]


def compress_aida(eng, seed):
    from repro.api import CompressionSpec, Engine
    t0 = time.perf_counter()
    aida = Engine(eng.cfg, params=eng.params, seed=seed).compress(
        CompressionSpec(mode="aida", density=0.25))
    check(aida.backend.name == "pallas", "aida does not serve on pallas",
          {})
    return aida, time.perf_counter() - t0


def phase_aida(eng, requests, seed):
    aida, compress_s = compress_aida(eng, seed)
    report = serve(aida, requests)[2]
    report["compress_s"] = compress_s
    return report


def phase_mesh(eng, requests):
    """The same requests on one device and on a 4-way model mesh."""
    from repro.launch.mesh import make_host_mesh
    one, w_one, report = serve(eng, requests)
    mesh = make_host_mesh(n_model=4)
    _, w_tp, report["mesh"] = serve(eng, requests, force=one, mesh=mesh)
    report["mesh"]["shape"] = dict(mesh.shape)
    agree(report, "mesh_vs_one", one, w_one, w_tp)
    return report


# ------------------------------------------------------------- kernels
#: max |kernel - reference| over max |reference|.  VPU kernels accumulate
#: in f32 and differ from the reference only in summation order; MXU
#: kernels may round f32 operands to bf16 in one pass (2^-9 relative per
#: product); paged attention rounds probabilities to bf16 on bf16 pages,
#: as the XLA path does.
TOL_VPU, TOL_MXU = 1e-4, 1e-2


def phase_kernels(cfg, seed):
    import jax
    import jax.numpy as jnp
    from repro import kvstore as kvs
    from repro.core.quant import int8_matmul_ref, quantize_int
    from repro.kernels import acsr_spmv as sp
    from repro.kernels import int8_matmul as i8
    from repro.kernels import lut_matmul as lm
    from repro.kernels import ref

    rng = np.random.default_rng(seed)
    rows = []

    def compare(name, tol, kernel, reference):
        t0 = time.perf_counter()
        got = np.asarray(jax.block_until_ready(kernel()), np.float64)
        seconds = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(), np.float64)
        err = float(np.abs(got - want).max() / np.abs(want).max())
        rows.append({"kernel": name, "rel_err": err, "tol": tol,
                     "compile_and_run_s": seconds,
                     "ok": bool(np.isfinite(got).all() and err <= tol)})

    d, f = cfg.d_model, cfg.d_ff
    # the widest sparse rows: the down projection at density 0.25
    w = (rng.normal(size=(d, f)) * (rng.random((d, f)) < 0.25)
         ).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(f, SLOTS)), jnp.float32)
    b = sp.block_encode(w)
    compare("acsr", TOL_VPU, lambda: sp.acsr_spmv(b, x),
            lambda: ref.blocked_acsr_spmv_ref(
                b.values, b.col_idx, b.row_nnz, x, b.block_rows)[:d])
    nz = w[w != 0]
    cents = np.concatenate([[0.0], np.quantile(
        nz, np.linspace(0.02, 0.98, 15))]).astype(np.float32)
    bc = sp.block_encode_coded(w, cents)
    compare("aida", TOL_VPU, lambda: sp.acsr_spmv(bc, x),
            lambda: ref.blocked_acsr_spmv_ref(
                jnp.take(bc.centroids, bc.values.astype(jnp.int32)),
                bc.col_idx, bc.row_nnz, x, bc.block_rows)[:d])

    xb = jnp.asarray(rng.normal(size=(SLOTS, d)), jnp.float32)
    packed = jnp.asarray(rng.integers(0, 256, (f, d // 2)), jnp.uint8)
    cb = jnp.asarray(np.sort(rng.normal(size=16)), jnp.float32)
    compare("codebook_lut", TOL_MXU, lambda: lm.lut_matmul(xb, packed, cb),
            lambda: ref.lut_matmul_ref(xb, packed, cb))
    xc = jnp.asarray(rng.integers(0, 16, (SLOTS, d)), jnp.uint8)
    lut = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
    compare("codebook_lut_product", TOL_MXU,
            lambda: lm.lut_product_matmul(xc, packed, lut),
            lambda: ref.lut_product_matmul_ref(xc, packed, lut, f))
    qt = quantize_int(jnp.asarray(rng.normal(size=(f, d)), jnp.float32))
    compare("int8", TOL_MXU, lambda: i8.int8_matmul(xb, qt.q, qt.scale),
            lambda: int8_matmul_ref(xb, qt))

    h, hkv, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim
    npp = MAX_LEN // PAGE
    n_pages = 1 + SLOTS * npp
    # a full table, two partial ones and one just past its first chunk
    cur = np.array([MAX_LEN - 1, MAX_LEN * 2 // 3, MAX_LEN // 3,
                    CHUNK + 1])[:SLOTS]
    table = 1 + rng.permutation(SLOTS * npp).reshape(SLOTS, npp)
    table[np.arange(npp)[None, :] > (cur // PAGE)[:, None]] = -1
    table = jnp.asarray(table, jnp.int32)
    shape = (n_pages, hkv, PAGE, dh)
    pools = {
        "bf16": kvs.PagedKV(
            jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
            jnp.asarray(rng.normal(size=shape), jnp.bfloat16)),
        "int8": kvs.PagedKV(
            jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, hkv)),
                        jnp.float32),
            jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, hkv)),
                        jnp.float32))}
    q = jnp.asarray(rng.normal(size=(SLOTS, h, dh)), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(SLOTS, h, CHUNK, dh)), jnp.float32)
    cur_j = jnp.asarray(cur, jnp.int32)
    q_pos = jnp.asarray(cur[:, None] - CHUNK + 1 + np.arange(CHUNK),
                        jnp.int32)
    win = jnp.int32(-1)
    for kvd, pool in pools.items():
        for pb in (1, 2, 4):
            compare(f"paged_decode_{kvd}_pb{pb}", TOL_MXU,
                    lambda: kvs.paged_attention_pallas(
                        q, pool, table, cur_j, win, pb=pb),
                    lambda: kvs.paged_attention_xla(
                        q, pool, table, cur_j, win))
        for qtile in (8, CHUNK):
            compare(f"paged_chunk_{kvd}_qt{qtile}", TOL_MXU,
                    lambda: kvs.paged_attention_pallas_chunk(
                        qc, pool, table, q_pos, win, pb=2, qt=qtile),
                    lambda: kvs.paged_attention_xla_chunk(
                        qc, pool, table, q_pos, win))
    report = {"checks": rows, "peak_bytes_in_use": peak_bytes()}
    bad = [r["kernel"] for r in rows if not r["ok"]]
    check(not bad, f"kernels outside tolerance: {bad}", report)
    return report


# ---------------------------------------------------------------- main
def run_phase(name, fn, *args) -> bool:
    t0 = time.perf_counter()
    try:
        report = fn(*args)
        ok = True
    except Exception as e:  # reported, and the run exits non-zero
        traceback.print_exc()
        report = {**getattr(e, "report", {}),
                  "error": f"{type(e).__name__}: {e}"}
        ok = False
    emit({"phase": name, "ok": ok, "seconds": time.perf_counter() - t0,
          **report})
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, requests and kernel inputs")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way tensor-parallel phases")
    args = ap.parse_args()
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.api import Engine
    from repro.configs import get
    from repro.kernels import ops
    cfg = get(ARCH)
    emit({"phase": "start", "arch": ARCH, "seed": args.seed,
          "device_kind": devices[0].device_kind, "devices": len(devices),
          "pallas_interpret": ops.pallas_interpret(),
          "compile_cache": cache_dir})
    if ops.pallas_interpret():
        print("chip_smoke: Pallas would run in interpret mode",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    eng = Engine(cfg, seed=args.seed)
    jax.block_until_ready(eng.params)
    emit({"phase": "init", "params_s": time.perf_counter() - t0,
          "peak_bytes_in_use": peak_bytes()})
    requests = make_requests(cfg.vocab, args.seed)
    if args.four_chips:
        ok = run_phase("mesh_dense", phase_mesh, eng, requests)
        ok &= run_phase("mesh_aida",
                        lambda: phase_mesh(compress_aida(eng, args.seed)[0],
                                           requests))
    else:
        ok = run_phase("dense", phase_dense, eng, requests)
        ok &= run_phase("int8_kv", phase_int8_kv, eng, requests)
        ok &= run_phase("aida", phase_aida, eng, requests, args.seed)
        ok &= run_phase("kernels", phase_kernels, cfg, args.seed)
    if not ok:
        return 1
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
