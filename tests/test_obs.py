"""repro.obs: unified tracing, metrics registry, and flight recorder.

Covers: tracer determinism (two same-seed serves — co-located and
disaggregated — export byte-identical Chrome traces), the zero-cost-off
property (a traced run's scheduling decisions are identical to an
untraced run's), Chrome export schema (role pids, slot tids, tick-
derived timestamps), the flight-recorder ring dumping the last-N events
on a forced HealthError and on a structured RequestFailed, the typed
counter/gauge/histogram registry, provenance stamps, ``obs.timeit``,
wall-phase timers, and the serve CLI's ``--trace``/``--json`` flags.

Also the sched.metrics edge cases the registry rewrite is gated by:
percentile/_dist on empty and single-element inputs, an all-unserved
outcome fold, and the stable ``summarize()`` key schema.
"""
import dataclasses
import json
import os

import jax
import pytest

from repro import kvstore as kvs
from repro import obs
from repro import resil as rsl
from repro import sched as schd
from repro.api import Request
from repro.api.session import Session
from repro.configs import get, reduced
from repro.disagg import DisaggConfig, DisaggSession
from repro.models import model as M
from repro.sched import metrics

CFG = reduced(get("llama3-8b"), n_layers=2, d_model=64, d_ff=128,
              vocab=256)
PS = 4
ML = 48


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(0))


def burst_arrivals(n=5, seed=0):
    wl = schd.WorkloadSpec.preset("burst", n_requests=n, vocab=CFG.vocab,
                                  seed=seed)
    return schd.generate(wl)


def replay(arrivals):
    return [(t, dataclasses.replace(r)) for t, r in arrivals]


def mk_disagg(params, tracer, resil=None):
    return DisaggSession(CFG, params,
                         disagg=DisaggConfig(prefill_slots=2,
                                             decode_slots=3),
                         max_len=ML, page_size=PS,
                         scheduler={"chunk": 4}, resil=resil, obs=tracer)


# ------------------------------------------------------------- registry
def test_registry_counter_gauge_histogram():
    reg = obs.Registry()
    reg.counter("requests").inc()
    reg.counter("requests").inc(2)
    reg.gauge("level").set(3)
    h = reg.histogram("lat")
    h.observe_many([1.0, 2.0, 3.0, 4.0])
    assert reg.counter("requests").value == 3
    assert reg.gauge("level").value == 3
    s = h.summary()
    # nearest-rank p50 of 4 values rounds up (round-half-even on 1.5)
    assert s["mean"] == 2.5 and s["p50"] == 3.0 and s["p99"] == 4.0
    # scaled summary (seconds -> ms)
    assert h.summary(scale=1000.0)["mean"] == 2500.0
    snap = reg.snapshot()
    assert snap["counters"] == {"requests": 3}
    assert snap["gauges"] == {"level": 3}
    assert snap["histograms"]["lat"]["mean"] == 2.5


def test_histogram_empty_and_single():
    h = obs.Histogram("x")
    assert h.summary() is None
    h.observe(7.0)
    assert h.summary() == {"mean": 7.0, "p50": 7.0, "p99": 7.0}


def test_percentile_edges():
    assert obs.percentile([], 50) is None
    assert obs.percentile([5.0], 0) == 5.0
    assert obs.percentile([5.0], 100) == 5.0
    assert obs.percentile([1.0, 2.0, 3.0], 100) == 3.0
    assert obs.percentile([1.0, 2.0, 3.0], 0) == 1.0


def test_provenance_stamp():
    p = obs.provenance(config="llama3-8b", mode="aida", seed=3,
                       backend="pallas", extra_field=1)
    for k in ("config", "mode", "seed", "backend", "jax", "git_sha",
              "timestamp"):
        assert k in p
    assert p["seed"] == 3 and p["extra_field"] == 1
    assert p["jax"] == jax.__version__


# ------------------------------------------------------- sched.metrics
def test_metrics_dist_empty_and_single():
    assert metrics._dist([]) is None
    d = metrics._dist([2.0])
    assert d["mean"] == 2.0 and d["p50"] == 2.0 and d["p99"] == 2.0


def test_metrics_outcomes_only_unserved():
    recs = [{"state": "unserved"}, {"state": "unserved"}]
    out = metrics._outcomes(recs)
    assert out == {"unserved": 2}


def test_summarize_key_schema(params):
    """The registry rewrite must keep summarize()'s key set stable —
    benchmarks, the CLI, and check_regression.py all read it by name."""
    sess = Session(CFG, params, batch_slots=2, max_len=ML, page_size=PS,
                   scheduler={"chunk": 4})
    sess.run_workload(replay(burst_arrivals(3)))
    m = metrics.summarize(sess.records, 1.0, sess.stats["steps"])
    assert set(metrics.SUMMARY_KEYS) <= set(m)
    assert set(m) - set(metrics.SUMMARY_KEYS) <= \
        set(metrics.SUMMARY_KEYS_CONDITIONAL)
    assert m["outcomes"] == {"completed": m["completed"]}
    m2 = metrics.summarize(sess.records, 1.0, sess.stats["steps"],
                           roles={"prefill": {"steps": 1, "busy_ticks": 1},
                                  "decode": {"steps": 1, "busy_ticks": 1},
                                  "_ticks": 2},
                           resil={"shed": 0})
    assert set(m2) - set(metrics.SUMMARY_KEYS) <= \
        set(metrics.SUMMARY_KEYS_CONDITIONAL)
    assert "roles" in m2 and "resil" in m2
    # no-requests fold stays total-function
    empty = metrics.summarize([], 0.0, 0)
    assert empty["requests"] == 0 and empty["tok_per_s"] is None


# --------------------------------------------------------------- timeit
def test_timeit_returns_best_per_call():
    calls = []

    def fn(x):
        calls.append(x)
        return x

    dt = obs.timeit(fn, 1, reps=2, inner=3, warmup=1)
    assert dt >= 0.0
    assert len(calls) == 1 + 2 * 3   # warmup + reps x inner

    with pytest.raises(ZeroDivisionError):
        obs.timeit(lambda: 1 / 0, reps=1)


def test_wall_timers_phases():
    t = obs.Tracer()
    with obs.span("decode", t):
        pass
    with obs.span("decode", t, step=1):
        pass
    with obs.span("prefill", t):
        pass
    with obs.span("untimed"):          # no live tracer: profiler only
        pass
    s = t.wall.summary()
    assert set(s) == {"decode", "prefill"}
    assert s["decode"]["calls"] == 2 and s["prefill"]["calls"] == 1
    assert abs(sum(v["share"] for v in s.values()) - 1.0) < 1e-6


# --------------------------------------------------------------- tracer
def test_null_tracer_is_free_and_silent():
    t = obs.NULL
    assert not t.enabled
    t.instant("req.submit", tick=0)
    t.span("step.decode", tick=0)
    assert t.crash("whatever") is None


def test_tracer_chrome_export_schema(tmp_path):
    t = obs.Tracer()
    t.instant("req.submit", tick=0, role="prefill", rid=1)
    t.span("step.decode", tick=2, role="decode", slot=1, active=1)
    doc = t.to_chrome()
    evs = doc["traceEvents"]
    roles = {e["args"]["name"]: e["pid"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert roles == {"prefill": 1, "decode": 2}
    span = next(e for e in evs if e["name"] == "step.decode")
    assert span["ph"] == "X" and span["ts"] == 2 * obs.trace.TICK_US
    assert span["dur"] == obs.trace.TICK_US and span["tid"] == 2
    assert span["args"]["tick"] == 2
    inst = next(e for e in evs if e["name"] == "req.submit")
    assert inst["ph"] == "i" and inst["s"] == "t" and inst["tid"] == 0
    p = tmp_path / "t.json"
    t.export(str(p))
    assert json.loads(p.read_text()) == doc


def test_traced_serve_replay_identical(params, tmp_path):
    """Two same-seed co-located serves emit byte-identical traces."""
    paths = []
    for i in range(2):
        t = obs.Tracer()
        sess = Session(CFG, params, batch_slots=2, max_len=ML,
                       page_size=PS, scheduler={"chunk": 4,
                                                "prefix_cache": True},
                       obs=t)
        sess.run_workload(replay(burst_arrivals(4)))
        p = tmp_path / f"co_{i}.json"
        t.export(str(p))
        paths.append(p)
        assert any(e["name"] == "step.decode" for e in t.events)
        assert any(e["name"] == "prefix.pin" for e in t.events)
    assert paths[0].read_bytes() == paths[1].read_bytes()


def test_traced_serve_does_not_change_behavior(params):
    """Tracing must observe, never steer: token streams and scheduler
    stats are identical with and without a live tracer."""
    plain = Session(CFG, params, batch_slots=2, max_len=ML, page_size=PS,
                    scheduler={"chunk": 4})
    rp = plain.run_workload(replay(burst_arrivals(4)))
    traced = Session(CFG, params, batch_slots=2, max_len=ML, page_size=PS,
                     scheduler={"chunk": 4}, obs=obs.Tracer())
    rt = traced.run_workload(replay(burst_arrivals(4)))
    assert [r.tokens for r in rp] == [r.tokens for r in rt]
    assert plain.stats["steps"] == traced.stats["steps"]
    assert plain.sched.stats == traced.sched.stats


def test_disagg_trace_covers_handoff_seams(params, tmp_path):
    traces = []
    for i in range(2):
        t = obs.Tracer()
        d = mk_disagg(params, t,
                      resil={"fault_plan": "drop-handoff:1"})
        d.run_workload(replay(burst_arrivals(4)), on_incomplete="warn")
        p = tmp_path / f"dis_{i}.json"
        t.export(str(p))
        traces.append(p.read_bytes())
        names = {e["name"] for e in t.events}
        assert {"handoff.enqueue", "handoff.deliver", "handoff.migrate",
                "step.prefill", "step.decode"} <= names
        roles = {e["role"] for e in t.events}
        assert {"prefill", "decode"} <= roles
    assert traces[0] == traces[1]


# ------------------------------------------------------ flight recorder
def test_flight_recorder_ring_and_dump(tmp_path):
    r = obs.FlightRecorder(capacity=3, out_dir=str(tmp_path))
    for i in range(5):
        r.record({"name": "alloc.pages", "tick": i})
    assert r.total == 5 and len(r.ring) == 3
    path = r.dump(reason="OutOfPages", context={"tick": 4})
    doc = json.loads(open(path).read())
    assert doc["reason"] == "OutOfPages"
    assert [e["tick"] for e in doc["events"]] == [2, 3, 4]
    assert doc["events_total"] == 5
    # a second dump gets a fresh sequence number, not an overwrite
    p2 = r.dump(reason="OutOfPages", context={})
    assert p2 != path and os.path.exists(p2)


def test_health_error_dumps_flight_recorder(params, tmp_path, monkeypatch):
    """A watchdog HealthError must leave a post-mortem dump holding the
    failing session's last events."""
    monkeypatch.chdir(tmp_path)
    rec = obs.FlightRecorder(capacity=64, out_dir=str(tmp_path))
    t = obs.Tracer(recorder=rec)
    sess = Session(CFG, params, batch_slots=2, max_len=ML, page_size=PS,
                   scheduler={"chunk": 4},
                   resil={"watchdog_every": 2}, obs=t)
    orig = rsl.health.audit_session

    def corrupt(s, extra_refs=None):
        return orig(s, extra_refs) + ["manufactured leak (test)"]

    monkeypatch.setattr(rsl.health, "audit_session", corrupt)
    with pytest.raises(rsl.HealthError):
        sess.run_workload(replay(burst_arrivals(3)))
    dumps = sorted(tmp_path.glob("flight_*.json"))
    assert dumps, "HealthError did not dump the flight recorder"
    doc = json.loads(dumps[0].read_text())
    assert doc["reason"] == "HealthError"
    assert doc["events"], "dump carries no events"
    assert any(e["name"] == "req.submit" for e in doc["events"])
    assert doc["context"]["role"] == "engine"


def test_request_failed_dumps_flight_recorder(params, tmp_path):
    rec = obs.FlightRecorder(capacity=32, out_dir=str(tmp_path))
    t = obs.Tracer(recorder=rec)
    sess = Session(CFG, params, batch_slots=2, max_len=ML, page_size=PS,
                   scheduler={"chunk": 4},
                   resil={"deadline_ticks": 1}, obs=t)
    sess.run_workload(replay(burst_arrivals(3)), on_incomplete="warn")
    assert sess.failed, "deadline_ticks=1 should fail requests"
    dumps = sorted(tmp_path.glob("flight_*RequestFailed*.json"))
    assert dumps
    doc = json.loads(dumps[0].read_text())
    assert doc["context"]["why"] == "deadline"
    assert any(e["name"] == "resil.fail" for e in doc["events"])


# ------------------------------------------------------------------ CLI
def test_serve_cli_trace_and_json(tmp_path):
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    trace = tmp_path / "trace.json"
    mjson = tmp_path / "metrics.json"
    report = tmp_path / "report.json"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "llama3-8b", "--requests", "3", "--max-new", "4",
         "--trace", str(trace), "--trace-ring", "32",
         "--json", str(mjson), "--report", str(report),
         "--slo", "ttft_p99=40,goodput=1.0"],
        env=dict(os.environ, PYTHONPATH=src, REPRO_AUTOTUNE="0",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")),
        capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "trace:" in out.stdout and "json:" in out.stdout
    assert "critical path" in out.stdout and "slo PASS" in out.stdout
    rep = json.loads(report.read_text())
    assert rep["schema"] == "repro.obs.analyze/v1"
    assert rep["slo"]["pass"] is True
    assert all(sum(r["segments"].values()) == r["span"]
               for r in rep["requests"].values())
    doc = json.loads(trace.read_text())
    assert doc["traceEvents"]
    names = {e["name"] for e in doc["traceEvents"]}
    assert "step.decode" in names and "req.finish" in names
    m = json.loads(mjson.read_text())
    assert set(m) >= {"provenance", "metrics", "pages", "failed"}
    assert m["provenance"]["config"] == "llama3-8b-smoke"
    assert m["metrics"]["completed"] == 3
    assert m["pages"]["leaked"] == 0
    phases = m["wall_phases"]
    assert phases["session.dispatch"]["calls"] >= 1
    assert phases["session.readback"]["calls"] >= 1
