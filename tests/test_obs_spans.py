"""The serving session's spans and counters on the profiler's clock: one
``session.step`` per model call with its six children, a step record
per call, the compile log, and ``op_scopes()`` for both step programs."""
import collections
import os

import jax
import pytest

from repro import obs
from repro.api import Engine, Request
from repro.configs import get, reduced

CHILDREN = {"session.admit", "session.pages", "session.feed",
            "session.dispatch", "session.readback", "session.sample"}


def _session(tracer=None):
    eng = Engine(reduced(get("qwen1.5-0.5b")))
    return eng.session(batch_slots=4, max_len=64, page_size=8,
                       kv_cache="paged", kv_dtype="bf16",
                       scheduler={"chunk": 8}, obs=tracer)


def _requests():
    return [Request(prompt=list(range(1, 10 + 5 * r)), max_new=3 + r,
                    rid=r) for r in range(5)]


def _host_spans(log_dir):
    """Every ``session.*`` event of the trace: (start, end, name, args)."""
    from jax.profiler import ProfileData
    path = next(os.path.join(root, f) for root, _, files in os.walk(log_dir)
                for f in files if f.endswith(".xplane.pb"))
    data = ProfileData.from_file(path)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
            for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name.startswith("session.")]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A traced serve: the session, a warm-up run first so the profiled
    run compiles nothing, and the profiled run's host spans."""
    tracer = obs.Tracer()
    sess = _session(tracer)
    sess.submit(Request(prompt=list(range(1, 12)), max_new=2, rid=100))
    sess.run()
    sess.step_records.clear()
    tracer.wall.seconds.clear()
    tracer.wall.calls.clear()
    steps0 = sess.stats["steps"]
    log_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(log_dir)
    try:
        for r in _requests():
            sess.submit(r)
        results = sess.run()
    finally:
        jax.profiler.stop_trace()
    return sess, tracer, results, steps0, _host_spans(log_dir)


def test_one_step_span_with_six_children_per_model_call(served):
    sess, _, _, steps0, spans = served
    calls = sess.stats["steps"] - steps0
    parents = [s for s in spans if s[2] == "session.step"
               and "kind" in s[3]]
    assert len(parents) == calls > 0
    assert sorted(p[3]["step"] for p in parents) == \
        list(range(steps0, steps0 + calls))
    assert {p[3]["kind"] for p in parents} == {"decode", "chunked"}
    for a, b, _, args in parents:
        kids = sorted((s for s in spans if s[2] != "session.step"
                       and a <= s[0] and s[1] <= b),
                      key=lambda s: s[0])
        assert {k[2] for k in kids} == CHILDREN
        assert all(k[3]["step"] == args["step"]
                   and k[3]["kind"] == args["kind"] for k in kids)
        # siblings, in order, none overlapping the next
        assert all(x[1] <= y[0] for x, y in zip(kids, kids[1:]))
        order = [k[2] for k in kids]
        assert order.index("session.dispatch") \
            < order.index("session.readback") < order.index("session.sample")


def test_step_records_count_each_call(served):
    sess, _, results, steps0, _ = served
    recs = list(sess.step_records)
    assert [r["step"] for r in recs] == \
        list(range(steps0, sess.stats["steps"]))
    vocab = sess.cfg.vocab
    for r in recs:
        per_slot = sess.chunk if r["kind"] == "chunked" else 1
        # both kinds read back one logits row per slot
        assert r["d2h_bytes"] == sess.slots * vocab * 4
        assert r["sampled"] == len(r["emitted"]) <= r["active"]
        assert r["tokens"] >= r["active"] > 0
        assert r["h2d_bytes"] >= sess.slots * per_slot * 4
    assert sum(r["pages_granted"] for r in recs) > 0
    tokens = collections.defaultdict(list)
    for r in recs:
        for rid, tok in r["emitted"]:
            tokens[rid].append(tok)
    assert dict(tokens) == {res.rid: res.tokens for res in results
                            if res.rid != 100}


def test_wall_phases_are_the_span_names(served):
    _, tracer, _, _, _ = served
    assert set(tracer.wall.seconds) == CHILDREN | {"session.step"}
    # the readback waits for the device: it is never free
    assert tracer.wall.seconds["session.readback"] > 0
    assert tracer.wall.calls["session.dispatch"] == \
        tracer.wall.calls["session.readback"]


def test_compile_log_stamps_each_new_program():
    import time
    import jax.numpy as jnp
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 5)))
    new = [e for e in obs.compile_events if e[0] >= t0]
    assert any(name.startswith("compile:") for _, name, _ in new)
    assert any(name.startswith("trace:") for _, name, _ in new)
    assert all(t0 <= t <= time.perf_counter() and s >= 0
               for t, _, s in new)


def test_op_scopes_map_both_programs_to_the_scopes():
    sess = _session()
    scopes = sess.op_scopes()
    assert set(scopes) == {"jit_serve_decode_step", "jit_serve_chunked_step"}
    for module, table in scopes.items():
        seen = {part for op in table.values()
                for part in op.split("/")[:-1]}
        assert {"kv.write", "kv.read", "attention", "proj",
                "logits"} <= seen, module


def test_spans_cost_little_with_the_profiler_off():
    """An inactive span is a few microseconds: the untraced serving path
    pays at most ~7 of them per model call."""
    import time
    n = 20000
    t = time.perf_counter()
    for i in range(n):
        with obs.span("session.feed", step=i, kind="decode"):
            pass
    assert (time.perf_counter() - t) / n < 50e-6
