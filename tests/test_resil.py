"""repro.resil: deterministic fault injection, deadlines/retry, and
graceful degradation across the serving stack.

Covers: FaultPlan purity (same (seed, preset) -> identical decisions
regardless of call order or instance), the bounded-drop redelivery
guarantee, config validation/coercion, watchdog audits (clean pass and
manufactured-leak detection), request deadlines becoming structured
RequestFailed results everywhere a request can wait, load shedding,
wedged-role drain-and-recover with bounded retries, handoff-timeout
fallback to co-located prefill on the decode role, the degradation
ladder demoting new sessions' KV to int8, never-fitting requests
failing structurally under ``on_incomplete="warn"``, and "unserved"
terminal records at max_steps exhaustion.

The ``test_chaos_*`` sweep is the CI chaos gate (multidevice workflow):
every built-in fault preset x 3 seeds on the burst workload through the
disaggregated engine must complete every request token-identical to the
fault-free run, leak zero pages on both pools, and replay with
identical counters.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro import kvstore as kvs
from repro import resil as rsl
from repro import sched as schd
from repro.api import Engine, Request
from repro.api.session import Session
from repro.configs import get, reduced
from repro.disagg import DisaggConfig, DisaggSession
from repro.models import model as M

CFG = reduced(get("llama3-8b"), n_layers=2, d_model=64, d_ff=128,
              vocab=256)
PS = 4
ML = 48


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(0))


def burst_arrivals(n=6, seed=0):
    wl = schd.WorkloadSpec.preset("burst", n_requests=n, vocab=CFG.vocab,
                                  seed=seed)
    return schd.generate(wl)


def replay(arrivals):
    return [(t, dataclasses.replace(r)) for t, r in arrivals]


def mk_disagg(params, resil, **kw):
    d = dict(prefill_slots=2, decode_slots=3)
    d.update(kw)
    return DisaggSession(CFG, params, disagg=DisaggConfig(**d),
                         max_len=ML, page_size=PS,
                         scheduler={"chunk": 4}, resil=resil)


@pytest.fixture(scope="module")
def clean_tokens(params):
    """Fault-free disagg tokens for the module's burst workload."""
    d = mk_disagg(params, None)
    return {r.rid: r.tokens
            for r in d.run_workload(replay(burst_arrivals()))}


def leaked(d: DisaggSession) -> int:
    return d.pre.alloc.in_use + d.dec.alloc.in_use


# ------------------------------------------------------------ FaultPlan
def test_fault_plan_parse_and_validation():
    p = rsl.FaultPlan.parse("drop-handoff:3")
    assert (p.preset, p.seed) == ("drop-handoff", 3)
    assert rsl.FaultPlan.parse("straggler").seed == 0
    with pytest.raises(ValueError, match="unknown fault preset"):
        rsl.FaultPlan.parse("gremlins:1")
    with pytest.raises(ValueError, match="PRESET:SEED"):
        rsl.FaultPlan.parse("straggler:x")


def test_fault_plan_decisions_are_pure():
    """Decisions are a pure function of (seed, preset, coordinates):
    two independently built plans agree on everything, call order is
    irrelevant, and a different seed disagrees somewhere."""
    a = rsl.FaultPlan.make("drop-handoff", seed=7)
    b = rsl.FaultPlan.make("drop-handoff", seed=7)
    coords = [(rid, att) for rid in range(20) for att in range(3)]
    # query b in reverse order — must not matter
    got_a = [a.drop_handoff(r, t) for r, t in coords]
    got_b = list(reversed([b.drop_handoff(r, t)
                           for r, t in reversed(coords)]))
    assert got_a == got_b
    assert [a.handoff_delay(r) for r in range(20)] == \
           [b.handoff_delay(r) for r in range(20)]
    c = rsl.FaultPlan.make("drop-handoff", seed=8)
    assert got_a != [c.drop_handoff(r, t) for r, t in coords]

    s1 = rsl.FaultPlan.make("straggler", seed=1)
    s2 = rsl.FaultPlan.make("straggler", seed=1)
    ticks = [(role, t) for role in ("prefill", "decode")
             for t in range(40)]
    assert [s1.step_fault(r, t) for r, t in ticks] == \
           [s2.step_fault(r, t) for r, t in ticks]


def test_drop_handoff_bounded_redelivery():
    """Delivery is guaranteed: past max_drops the plan must say no."""
    p = rsl.FaultPlan.make("drop-handoff", seed=0, drop_p=1.0)
    for rid in range(10):
        assert p.drop_handoff(rid, 0)
        assert not p.drop_handoff(rid, p.params["max_drops"])


def test_page_holdback_only_inside_window():
    p = rsl.FaultPlan.make("page-spike", seed=0, start=5, span=3,
                           jitter=0, frac=0.5)
    assert p.page_holdback(20, 4, role="decode") == 0
    assert p.page_holdback(20, 5, role="decode") == 10
    assert p.page_holdback(20, 8, role="decode") == 0
    assert p.page_holdback(20, 5, role="prefill") == 0
    assert p.page_holdback(20, 5, role="engine") == 10   # co-located


def test_resil_config_validation_and_coercion():
    with pytest.raises(ValueError, match="deadline_ticks"):
        rsl.ResilConfig(deadline_ticks=0)
    with pytest.raises(ValueError, match="max_retries"):
        rsl.ResilConfig(max_retries=-1)
    with pytest.raises(ValueError, match="wedge_ticks"):
        rsl.ResilConfig(wedge_ticks=0)
    with pytest.raises(ValueError, match="shed_watermark"):
        rsl.ResilConfig(shed_watermark=0.0)
    assert rsl.ResilConfig.coerce("role-stall:2").fault_plan.seed == 2
    assert rsl.ResilConfig.coerce(True).fault_plan is None
    cfg = rsl.ResilConfig.coerce(
        {"fault_plan": {"preset": "page-spike", "seed": 1,
                        "params": {"frac": 0.9}}})
    assert cfg.fault_plan.params["frac"] == 0.9
    assert rsl.ResilConfig.coerce(cfg) is cfg


# --------------------------------------------------------------- health
def test_watchdog_audit_passes_and_catches_leak(params):
    sess = Session(CFG, params, batch_slots=2, max_len=ML, page_size=PS)
    sess.submit(Request(prompt=[2, 3, 4, 5, 6], max_new=3, rid=0))
    sess.run()
    assert rsl.audit_allocator(sess.alloc) == []
    assert rsl.audit_session(sess) == []   # drained: clean
    pid = sess.alloc.alloc()           # manufactured leak: no slot ref
    issues = rsl.audit_session(sess)
    assert issues and "refcount" in issues[0]
    with pytest.raises(rsl.HealthError, match="watchdog audit failed"):
        rsl.Watchdog(1).audit(sess)
    sess.alloc.free([pid])
    assert rsl.audit_session(sess) == []


def test_watchdog_audits_during_run(params):
    arrivals = burst_arrivals()
    d = mk_disagg(params, {"watchdog_every": 2})
    toks = {r.rid: r.tokens for r in d.run_workload(replay(arrivals))}
    base = mk_disagg(params, None)
    ref = {r.rid: r.tokens for r in base.run_workload(replay(arrivals))}
    assert toks == ref                 # auditing changes nothing
    assert d.resil.stats["watchdog_audits"] > 0
    assert leaked(d) == 0


# ------------------------------------------------- deadlines / shedding
def test_deadline_expiry_structured_failures(params):
    d = mk_disagg(params, {"deadline_ticks": 5})
    res = d.run_workload(replay(burst_arrivals()), on_incomplete="warn")
    assert len(res) + len(d.failed) == 6
    assert d.failed and all(f.reason == "deadline" for f in d.failed)
    assert d.resil.stats["deadline_miss"] == len(d.failed)
    assert leaked(d) == 0
    fr = [r for r in d.records if r["state"] == "failed"]
    assert {r["failed_reason"] for r in fr} == {"deadline"}
    m = schd.summarize(d.records, 1.0, 1, resil=d.resil_summary())
    assert m["outcomes"]["failed_by_reason"]["deadline"] == len(d.failed)
    assert m["resil"]["deadline_miss"] == len(d.failed)


def test_per_request_deadline_overrides_config(params):
    sess = Session(CFG, params, batch_slots=1, max_len=ML, page_size=PS,
                   resil={"deadline_ticks": 500})
    sess.submit(Request(prompt=[2] * 8, max_new=8, rid=0,
                        deadline_ticks=1))
    sess.submit(Request(prompt=[3] * 4, max_new=2, rid=1))
    res = sess.run(on_incomplete="warn")
    assert [f.rid for f in sess.failed] == [0]
    assert sess.failed[0].reason == "deadline"
    assert [r.rid for r in res] == [1]
    assert sess.alloc.in_use == 0


def test_shed_load_youngest_never_admitted(params, clean_tokens):
    d = mk_disagg(params, {"shed_watermark": 0.25})
    res = d.run_workload(replay(burst_arrivals()), on_incomplete="warn")
    assert d.resil.stats["shed"] > 0
    assert all(f.reason == "shed" and not f.tokens for f in d.failed)
    # survivors are token-identical: shedding rejects, never corrupts
    assert all(clean_tokens[r.rid] == r.tokens for r in res)
    assert leaked(d) == 0


# ------------------------------------------------------ chaos (CI gate)
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("preset", ["drop-handoff", "role-stall",
                                    "page-spike", "straggler"])
def test_chaos_preset_parity_and_replay(params, clean_tokens, preset,
                                        seed):
    """The hard resilience contract, per (preset, seed): every request
    completes, completed streams are token-identical to the fault-free
    run, zero pages leak on either pool, and a same-seed replay produces
    identical counters and tokens."""
    runs = []
    for _ in range(2):
        d = mk_disagg(params, {"fault_plan": f"{preset}:{seed}",
                               "max_retries": 2, "watchdog_every": 4})
        res = d.run_workload(replay(burst_arrivals()),
                             on_incomplete="warn")
        s = d.resil_summary()
        runs.append(({r.rid: r.tokens for r in res}, leaked(d),
                     {k: s[k] for k in rsl.ResilState.COUNTERS},
                     s.get("faults", {})))
        assert not d.failed
    toks, leak, counters, faults = runs[0]
    assert toks == clean_tokens, f"{preset}:{seed} diverged"
    assert leak == 0
    assert runs[0] == runs[1], f"{preset}:{seed} replay diverged"


# ---------------------------------------------- recovery / degradation
def test_handoff_timeout_falls_back_to_decode_role(params, clean_tokens):
    d = mk_disagg(params, {"fault_plan": "drop-handoff:0",
                           "handoff_timeout": 2, "max_retries": 2})
    res = d.run_workload(replay(burst_arrivals()), on_incomplete="warn")
    assert d.resil.stats["handoff_fallbacks"] > 0
    assert {r.rid: r.tokens for r in res} == clean_tokens
    assert any(r.get("degraded") == "colocated-prefill"
               for r in d.records)
    assert d.dec.stats["preemptions"] == 0   # reservation discipline held
    assert leaked(d) == 0


def test_wedged_role_drain_and_recover(params, clean_tokens):
    """A prefill role stalled far past wedge_ticks gets drained: its
    slots requeue through the retry path and either complete with
    oracle tokens or fail structurally once retries exhaust."""
    plan = {"preset": "role-stall", "seed": 0,
            "params": {"role": "prefill", "start": 2, "span": 12,
                       "jitter": 0}}
    d = mk_disagg(params, {"fault_plan": plan, "max_retries": 3,
                           "watchdog_every": 2, "wedge_ticks": 3})
    res = d.run_workload(replay(burst_arrivals()), on_incomplete="warn")
    r = d.resil.stats
    assert r["watchdog_recoveries"] > 0 and r["retries"] > 0
    assert all(clean_tokens[x.rid] == x.tokens for x in res)
    assert all(f.reason == "retries_exhausted" for f in d.failed)
    assert len(res) + len(d.failed) == 6
    assert leaked(d) == 0


def test_degrade_ladder_demotes_next_session_kv(params):
    plan = {"preset": "page-spike", "seed": 0,
            "params": {"frac": 0.8, "span": 500, "start": 2,
                       "jitter": 0}}
    d = mk_disagg(params, {"fault_plan": plan, "degrade_kv": True,
                           "degrade_sustain_ticks": 3})
    d.run_workload(replay(burst_arrivals()), on_incomplete="warn",
                   max_steps=400)
    assert d.resil.degrade.level == 2
    assert d.resil.next_kv_dtype("bf16") == "int8"
    assert leaked(d) == 0
    # next-session boundary: Engine.session consults the live state
    eng = Engine(CFG, params=params)
    s2 = eng.session(max_len=ML, kv_cache="paged", page_size=PS,
                     resil=d.resil)
    assert s2.kv_dtype == "int8"
    s2.submit(Request(prompt=[2, 3, 4], max_new=2, rid=0))
    s2.run()
    assert d.resil.stats["degraded_admissions"] > 0


# -------------------------------------------- structured terminal states
def test_oversized_request_warns_and_fails_structurally(params):
    """Satellite: a handoff that can NEVER fit the decode pool names the
    request, its page need, and the pool size — and with
    ``on_incomplete="warn"`` becomes a RequestFailed, not a hang."""
    d = DisaggSession(CFG, params,
                      disagg=DisaggConfig(decode_pool_pages=4),
                      max_len=ML, page_size=PS, scheduler={"chunk": 4},
                      resil=True)
    d.submit(Request(prompt=list(range(1, 21)), max_new=8, rid=7))
    with pytest.warns(RuntimeWarning, match=r"request 7 needs \d+ pages"):
        res = d.run(on_incomplete="warn")
    assert res == []
    assert [f.rid for f in d.failed] == [7]
    assert d.failed[0].reason == "oversized"
    assert leaked(d) == 0
    # without the resil layer the same situation still raises loudly
    d2 = DisaggSession(CFG, params,
                       disagg=DisaggConfig(decode_pool_pages=4),
                       max_len=ML, page_size=PS, scheduler={"chunk": 4})
    d2.submit(Request(prompt=list(range(1, 21)), max_new=8, rid=0))
    with pytest.raises(kvs.OutOfPages, match="decode page pool"):
        d2.run()


def test_unserved_records_at_max_steps(params):
    """Satellite: requests still queued/pending when max_steps runs out
    get a terminal "unserved" state instead of vanishing."""
    arrivals = [(0, Request(prompt=[2] * 8, max_new=6, rid=0)),
                (1, Request(prompt=[3] * 8, max_new=6, rid=1)),
                (500, Request(prompt=[4] * 4, max_new=2, rid=2))]
    sess = Session(CFG, params, batch_slots=1, max_len=ML, page_size=PS,
                   scheduler={"chunk": 4}, resil=True)
    with pytest.warns(RuntimeWarning, match="max_steps"):
        sess.run_workload(arrivals, max_steps=3, on_incomplete="warn")
    by_rid = {r["rid"]: r for r in sess.records}
    assert len(by_rid) == 3
    assert by_rid[2]["state"] == "unserved"      # never arrived
    assert by_rid[2]["n_generated"] == 0
    states = {r["state"] for r in sess.records}
    assert states <= {"completed", "unserved"} and "unserved" in states
    m = schd.summarize(sess.records, 1.0, 3)
    assert m["outcomes"]["unserved"] >= 2

    d = mk_disagg(params, True)
    with pytest.warns(RuntimeWarning, match="max_steps"):
        d.run_workload([(0, Request(prompt=[2] * 8, max_new=6, rid=0)),
                        (900, Request(prompt=[3] * 4, max_new=2, rid=1))],
                       max_steps=2, on_incomplete="warn")
    st = {r["rid"]: r["state"] for r in d.records}
    assert st[0] == "unserved" and st[1] == "unserved"


def test_resil_none_is_exact_noop(params):
    """resil=None must be byte-identical to the pre-resil path: no
    record fields change meaning, no counters appear."""
    arrivals = burst_arrivals()
    a = Session(CFG, params, batch_slots=2, max_len=ML, page_size=PS,
                scheduler={"chunk": 4})
    b = Session(CFG, params, batch_slots=2, max_len=ML, page_size=PS,
                scheduler={"chunk": 4}, resil=None)
    ra = a.run_workload(replay(arrivals))
    rb = b.run_workload(replay(arrivals))
    assert [r.tokens for r in ra] == [r.tokens for r in rb]
    assert a.resil is None and a.resil_summary() is None
    assert all(r["state"] == "completed" for r in a.records)


# ----------------------------------------------------------- CLI / bench
def test_serve_cli_accepts_resil_flags(tmp_path):
    import subprocess
    import sys
    import os
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    # the serve CLI keeps a compile cache; keep it out of the checkout
    env = dict(os.environ, PYTHONPATH=src,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "llama3-8b", "--requests", "3", "--max-new", "4",
         "--fault-plan", "straggler:1", "--deadline-ticks", "64",
         "--max-retries", "1"],
        env=env, capture_output=True,
        text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "resil:" in out.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "llama3-8b", "--fault-plan", "nope:1"],
        env=env, capture_output=True,
        text=True, timeout=600)
    assert bad.returncode != 0
    assert "unknown fault preset" in bad.stderr
