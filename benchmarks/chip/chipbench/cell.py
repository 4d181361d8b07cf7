"""One run of one cell: build the configuration from the seed, warm up,
drive the traffic for the window, check what was served against the
reference, and gather the metrics the cell reports."""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
from typing import Callable, Dict, Optional

from chipbench import check, device, serve, traffic, work
from chipbench.layout import CHECKOUT, Layout

#: the traced part of a --trace 1 window: from a third of the way in,
#: this many seconds (at most a third of the window)
TRACE_SECONDS = 4.0


class Profiler:
    """Starts and stops a ``jax.profiler`` trace into a scratch directory
    under TMPDIR, with the ``bench.window`` host span around it."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self._span = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def xplane(self) -> str:
        for root, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        raise FileNotFoundError(f"no .xplane.pb under {self.dir}")

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class RunView:
    """What a metric reader sees (``metrics/<name>.py``: ``read(run)``)."""
    served: serve.Served
    setup_s: float
    config: Dict
    dims: object
    model: object
    peaks: Optional[Dict]
    trace: Optional[object]          # chipbench.tracefile.Reduction
    _work: object = None

    def work(self):
        """{step index: work.StepWork}, or None where it cannot be
        rebuilt (see ``chipbench.work``)."""
        if self._work is None:
            self._work = work.rebuild(self.served, self.model, self.dims,
                                      self.config["serving"]["chunk"]) \
                or {}
        return self._work or None

    def traced_steps(self):
        """Steps the trace holds whole, with their work (or None)."""
        steps = self.work()
        if steps is None or self.served.traced is None:
            return None
        t0, t1 = self.served.traced
        return [steps[s.index] for s in self.served.steps
                if s.start >= t0 and s.end <= t1 and s.index in steps]


def _program():
    """Import the program under test from the checkout's ``src``."""
    src = os.path.join(CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.api as api
    return api


def _check_widths(arch, dims) -> None:
    """The program's configuration must be the file's, width for width."""
    want = {"d_model": dims.d, "d_ff": dims.f, "n_layers": dims.layers,
            "n_heads": dims.heads, "n_kv": dims.kv_heads,
            "head_dim": dims.head_dim, "vocab": dims.vocab,
            "rope_theta": dims.theta}
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        raise ValueError(f"program config {arch.name} {got} differs from "
                         f"the benchmark's {want}")


def build(api, conf: Dict, dims, model, seed: int, arch=None):
    """The engine as served: weights from the seed, compressed in place
    where the configuration says so."""
    from repro.configs import get
    arch = arch or get(conf["arch"])
    _check_widths(arch, dims)
    eng = api.Engine(arch, params=model.make_weights(dims, seed))
    comp = conf.get("compression")
    if comp:
        eng.compress(api.CompressionSpec(
            mode=comp["mode"], density=comp["density"],
            k=comp["codebook_size"], kmeans_iters=comp["kmeans_iters"],
            block_rows=comp["block_rows"]))
    return eng


def open_session(eng, conf: Dict):
    s = conf["serving"]
    return eng.session(batch_slots=s["slots"], max_len=s["max_len"],
                       page_size=s["page_size"],
                       kv_pool_pages=s["kv_pool_pages"],
                       kv_dtype=s["kv_dtype"],
                       scheduler={"chunk": s["chunk"]})


def warm_up(api, sess, conf: Dict) -> None:
    """Compile the two step shapes the window runs: one request of
    ``chunk + 1`` prompt tokens and two new tokens takes a chunked step
    and a decode step."""
    c = conf["serving"]["chunk"]
    sess.submit(api.Request(prompt=list(range(1, c + 2)), max_new=2,
                            rid=-1))
    sess.run()


def run_cell(layout: Layout, name: str, seed: int, seconds: float,
             traced: bool, *, process_start: float, control: bool = False,
             need_chip: bool = True,
             on_session: Optional[Callable] = None,
             arch=None) -> Dict:
    """One run; returns the result line's fields (``checks`` last)."""
    cell = layout.cell(name)
    conf = layout.config(cell["config"])
    mix = traffic.mix_params(layout.traffic(cell["traffic"]),
                             layout.workload(name))
    model = layout.model(conf["family"])
    dims = model.Dims(conf)
    readers = [(m, layout.metric(m["name"]))
               for m in layout.metrics_for(name, traced)]
    import jax
    if need_chip:
        devices = device.require_tpu(cell["chips"])
        peaks = device.peaks_for(devices[0].device_kind)
    else:
        devices, peaks = jax.devices()[:cell["chips"]], None
    api = _program()
    if need_chip:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        # small programs of the session (page-table writes) are cached
        # too, so that no later run compiles inside its window
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    eng = build(api, conf, dims, model, seed, arch)
    sess = open_session(eng, conf)
    warm_up(api, sess, conf)
    if on_session is not None:
        on_session(sess)
    profiler = Profiler() if traced else None

    def make_request(r):
        return api.Request(prompt=list(r.prompt), max_new=r.max_new,
                           temperature=r.temperature, rid=r.index)

    loop = serve.Loop(sess, mix, traffic.stream(mix, seed, dims.vocab),
                      make_request=make_request,
                      annotate=jax.profiler.TraceAnnotation,
                      profiler=profiler)
    trace_s = min(TRACE_SECONDS, seconds / 3)
    served = loop.run(seconds, trace_at=seconds / 3 if traced else None,
                      trace_s=trace_s)
    dev = device.describe(devices)
    reduction = None
    try:
        if traced and served.traced is not None:
            from chipbench import tracefile
            reduction = tracefile.reduce(profiler.xplane())
    finally:
        if profiler is not None:
            profiler.close()
    picks = check.sample(served.logs, seed, int(mix["check_tokens"]),
                         int(mix["check_requests"]), after=served.window[0])
    del loop, sess, eng
    gc.collect()
    ref_w = model.reference_weights(dims, seed)
    gaps = check.gaps(model, dims, ref_w, picks,
                      pad_to=conf["serving"]["max_len"], control=control)
    del ref_w
    verdict = check.verdict(gaps["served"], mix["limits"])
    if control:
        # the control in the program's place: its gaps decide ``correct``
        served_verdict = verdict
        verdict = check.verdict(gaps["control"], mix["limits"])
    run = RunView(served, served.window[0] - process_start, conf, dims,
                  model, peaks, reduction)
    metrics = {}
    for spec, reader in readers:
        value = reader.read(run)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    due = attempted(served, mix)
    failed = sum(1 for log in due if log.failed
                 or (mix["loop"] == "open" and not log.times))
    out = {"correct": verdict["ok"], "attempted": len(due),
           "failed": failed, "metrics": metrics, "device": dev}
    if traced and reduction is not None:
        dev["busy_s"] = reduction.busy_s
        dev["window_s"] = reduction.window_s
        out["breakdown"] = reduction.breakdown()
    if control:
        out["control"] = {
            "served_max_gap":
                served_verdict["checks"]["max_logit_gap"]["value"],
            "served_correct": served_verdict["ok"],
            "control_max_gap": verdict["checks"]["max_logit_gap"]["value"],
            "tokens": int(gaps["served"].size)}
    out["checks"] = verdict["checks"]
    return out


def attempted(served, mix: Dict):
    """The requests a run answers for: open loop, those due in the
    window; closed loop, those the window worked on."""
    if mix["loop"] == "open":
        d0, d1 = served.due_window
        return [log for log in served.logs.values() if d0 <= log.due < d1]
    w0, w1 = served.window
    return [log for log in served.logs.values() if log.due < w1
            and not (log.finished and log.times[-1] <= w0)]
