"""The reduction by the program's own spans and scopes
(``chipbench.programtrace``), its readings, the compile-count reader,
and the session's step records against the work the harness rebuilds."""
import os
import types

import chipbench_testkit as kit
import pytest

from chipbench import programtrace as pt
from chipbench import tracefile

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny_aida.xplane.pb.gz")
WINDOW = (0, 1000, tracefile.WINDOW_SPAN)


def _step(a, b, children, step=0):
    """A session.step span [a, b) and its children (name, start, end),
    with the Python frames a real trace holds inside them."""
    out = [(a, b, "session.step")]
    for name, x, y in children:
        out += [(x, y, f"session.{name}"), (x, y, "$session.py:1 frame")]
    return out


def _events():
    host = [WINDOW, (0, 1000, "bench.step")]
    host += _step(0, 450, [("admit", 0, 20), ("pages", 20, 60),
                           ("feed", 60, 80), ("dispatch", 80, 100),
                           ("readback", 100, 400), ("sample", 400, 440)])
    host += _step(600, 900, [("admit", 600, 610), ("dispatch", 610, 620),
                             ("readback", 620, 880)], step=1)
    mods = [(90, 350, "jit_serve_decode_step(123)"),
            (500, 520, "jit_scatter(7)"),
            (615, 860, "jit_serve_chunked_step(456)")]
    ops = [(90, 350, "%while.3 = (s32[]) while()"),
           (100, 200, "%copy.49 = bf16[1,8]{1,0} copy()"),
           (200, 300, "%fusion.157 = bf16[5,8]{1,0} fusion()"),
           (300, 340, "%convolution.37 = f32[2,9]{1,0} convolution()"),
           (500, 520, "%scatter.1 = s32[2,4]{1,0} scatter()"),
           (615, 860, "%fusion.157 = f32[4]{0} fusion()")]
    scopes = {
        "jit_serve_decode_step": {
            "while.3": "jit(serve_decode_step)/while",
            "copy.49": "jit(serve_decode_step)/while/body/dynamic_slice",
            "fusion.157": "jit(serve_decode_step)/while/body/attention/"
                          "kv.read/jit(_take)/gather",
            "convolution.37": "jit(serve_decode_step)/logits/dot_general"},
        "jit_serve_chunked_step": {
            "fusion.157": "jit(serve_chunked_step)/while/body/proj/dot"}}
    return [(mods, ops)], [host], scopes


def test_idle_is_laid_at_the_innermost_session_span():
    devices, host, scopes = _events()
    red = pt.reduce_events(devices, host, scopes)
    ns = {k: round(v * 1e9) for k, v in red.program_idle_s.items()}
    assert ns == {"session.admit": 30, "session.pages": 40,
                  "session.feed": 20, "session.dispatch": 10 + 5,
                  "session.readback": 50 + 20, "session.sample": 40,
                  "session.step": 10 + 20, pt.OUTSIDE: 50 + 80 + 100}
    # with the frames' own reduction: the idle split fills the same idle
    plain = tracefile.reduce_events([(
        [m[:2] for m in devices[0][0]], devices[0][1])], host)
    assert red.busy_s == pytest.approx(plain.busy_s)
    assert sum(red.program_idle_s.values()) == \
        pytest.approx(plain.window_s - plain.busy_s)


def test_idle_shares_and_outside_add_up_to_the_device_idle_share():
    devices, host, scopes = _events()
    red = pt.reduce_events(devices, host, scopes)
    got = pt.readings(red, [], [], (0.0, 1.0))
    idle = 100.0 * (1 - red.busy_s / red.window_s)
    outside = 100.0 * red.program_idle_s[pt.OUTSIDE] / red.window_s
    shares = [got[m] for m in pt.IDLE_SHARES]
    assert sum(shares) + outside == pytest.approx(idle)
    assert got["sampler.idle_share"] == pytest.approx(11.0)
    assert got["kv.alloc_idle_share"] == pytest.approx(4.0)
    assert got["sched.idle_share"] == pytest.approx(9.5)


def test_op_time_is_laid_at_its_scope_by_program_name():
    devices, host, scopes = _events()
    red = pt.reduce_events(devices, host, scopes)
    ns = {k: round(v * 1e9) for k, v in red.scope_s.items()}
    # the same instruction name means another op in another program;
    # a program op_scopes does not know, and a scope-less op, have none
    assert ns == {"kv": 100, "logits": 40, "proj": 245,
                  pt.NO_SCOPE: 100 + 20 + 20}
    top = dict((k, round(v * 1e9)) for k, v in red.unscoped_top())
    assert top == {"jit_serve_decode_step copy.49 bf16[1,8]": 100,
                   "jit_scatter scatter.1 s32[2,4]": 20,
                   "jit_serve_decode_step while.3 (s32[])": 20}
    got = pt.readings(red, [], [], (0.0, 1.0))
    assert got["step.kv_share"] == pytest.approx(100.0 * 100 / 525)


@pytest.mark.parametrize("op, scope", [
    ("jit(f)/while/body/attention/kv.read/jit(_take)/gather", "kv"),
    ("jit(f)/while/body/closed_call/kv.write/scatter", "kv"),
    ("jit(f)/while/body/attention/bkgqd,bpkcd->bkgqpc/dot_general",
     "attention"),
    ("jit(f)/while/body/proj/dot_general", "proj"),
    ("jit(f)/logits/convert_element_type", "logits"),
    ("jit(f)/while/body/dynamic_slice", pt.NO_SCOPE),
    ("kv.write", pt.NO_SCOPE)])
def test_scope_of_takes_the_innermost_scope(op, scope):
    assert pt.scope_of(op) == scope


def test_d2h_bytes_per_token_reads_the_steps_inside_the_window():
    steps = [types.SimpleNamespace(index=i, start=float(i),
                                   end=float(i) + 0.5) for i in range(4)]
    records = [{"step": i, "sampled": s, "d2h_bytes": b}
               for i, s, b in [(0, 4, 999), (1, 2, 100), (2, 0, 300),
                               (3, 8, 999)]]
    got = pt.readings(None, records, steps, (0.9, 2.6))
    assert got == {"sampler.d2h_bytes_per_token": pytest.approx(200.0)}
    assert pt.readings(None, records, steps, (5.0, 6.0)) == {}


def test_compile_count_reads_the_programs_log_inside_the_window():
    from chipbench.layout import Layout
    from repro import obs
    reader = Layout().metric("engine.compiles_in_window")
    run = types.SimpleNamespace(
        served=types.SimpleNamespace(window=(1e9, 1e9 + 10)))
    before = list(obs.compile_events)
    try:
        obs.compile_events.extend([
            (1e9 + 1, "compile:jit(scatter)", 0.1),
            (1e9 + 2, "trace:scatter", 0.1),
            (1e9 + 3, "compile:jit(add)", 0.1),
            (1e9 + 11, "compile:jit(late)", 0.1)])
        assert reader.read(run) == 2.0
    finally:
        obs.compile_events.clear()
        obs.compile_events.extend(before)


def test_recorded_trace_without_session_spans_is_all_outside():
    """A trace of a program without spans or scopes (the fixture): its
    idle is all outside the program and its ops have no scope."""
    red = pt.reduce(FIXTURE, {})
    plain = tracefile.reduce(FIXTURE)
    assert set(red.program_idle_s) == {pt.OUTSIDE}
    assert red.program_idle_s[pt.OUTSIDE] == \
        pytest.approx(plain.window_s - plain.busy_s)
    assert set(red.scope_s) == {pt.NO_SCOPE}
    assert red.scope_s[pt.NO_SCOPE] == \
        pytest.approx(sum(plain.op_s.values()))


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return kit.make_layout(str(tmp_path_factory.mktemp("tinybench")))


def test_step_records_match_the_rebuilt_work(layout):
    """Without preemption the session's own counts of each step are the
    tokens and sampled positions the harness rebuilds from its logs."""
    import jax
    from chipbench import cell, serve, traffic, work
    conf = layout.config("tiny-dense")
    model = layout.model(conf["family"])
    dims = model.Dims(conf)
    mix = traffic.mix_params(layout.traffic("gen"),
                             layout.workload("tiny-dense.gen"))
    api = cell._program()
    eng = cell.build(api, conf, dims, model, 5, kit.tiny_arch())
    sess = cell.open_session(eng, conf)
    cell.warm_up(api, sess, conf)
    loop = serve.Loop(sess, mix, traffic.stream(mix, 5, dims.vocab),
                      make_request=lambda r: api.Request(
                          prompt=list(r.prompt), max_new=r.max_new,
                          rid=r.index),
                      annotate=jax.profiler.TraceAnnotation)
    served = loop.run(1.0)
    assert sess.stats["preemptions"] == 0
    rebuilt = work.rebuild(served, model, dims, conf["serving"]["chunk"])
    records = {r["step"]: r for r in sess.step_records}
    assert rebuilt and set(rebuilt) <= set(records)
    # the rebuild cannot see the prompt of a request that has no token
    # yet: compare the steps before the first such admission
    cut = min((log.admit_step for log in served.logs.values()
               if log.admit_step is not None and not log.steps),
              default=max(rebuilt) + 1)
    assert cut > min(rebuilt) + 10
    for index, w in rebuilt.items():
        if index >= cut:
            continue
        assert (records[index]["tokens"], records[index]["sampled"]) == \
            (w.tokens, w.sampled), index


def test_readings_tool_runs_a_tiny_cell(layout):
    import sys
    sys.path.insert(0, kit.CHIP)
    import program_readings
    import time
    line = program_readings.read_cell(
        layout, "tiny-dense.gen", 9, 1.0, process_start=time.perf_counter(),
        need_chip=False, arch=kit.tiny_arch())
    assert line["result"]["correct"]
    assert line["end_to_end"]["output_tok_s"] > 0
    got = line["readings"]
    assert got["sampler.d2h_bytes_per_token"] > 0
    assert got["engine.compiles_in_window"] >= 0
    # the CPU has no device plane: no idle split, no op time
    assert line["scope_s"] == {}
