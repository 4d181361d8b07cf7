"""A Mellum2-shaped model served through ``Engine.session`` against the
plain float32 reference (``benchmarks/chip/models/mellum2.py``), at a
tiny size: 4 layers (three windowed at 8, then one full with YaRN),
GQA 4:1, 8 experts of which this chip holds 4, top 2.

* the two 4-expert shares of one expert layer add up to the uncut
  layer, and padded rows are neither routed, computed nor counted;
* chunked prefill then paged decode, past the window and through the
  YaRN layer, gives the reference's logits within ``TOL_LOGITS``, which
  the float8 control fails;
* a request's logits are the same served alone and in a full batch
  (dropless routing);
* the windowed paged attention (decode and chunk, XLA and Pallas) agrees
  with a plain masked reference at a window below the context;
* qwen1.5's RoPE is the same program as before the per-layer tables.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kvstore as kvs
from repro.api import Engine, Request
from repro.configs import get, reduced
from repro.configs.base import ArchConfig, MoECfg, YaRN
from repro.models import moe as moe_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: widest |program - reference| logit the served path may show (logits
#: have unit scale).  bf16 compute of the f32 weights reads 0.045-0.054
#: on seeds whose routing has no near-tie; the float8 control reads
#: 0.95-1.49, leaving out YaRN 0.48-1.21 and a window one wider 2.0-2.9.
#: A seed whose router puts the 2nd and 3rd expert within 5e-4 of each
#: other flips the choice under bf16 and reads 1.16 at that position, so
#: the test pins a seed without such a tie.
TOL_LOGITS = 0.25

HELD = (0, 1, 2, 3)
YARN = YaRN(factor=4.0, original_max_position=16, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.1386294361119891)
TINY = ArchConfig(
    name="mellum2-tiny", family="moe", n_layers=4, d_model=64, n_heads=8,
    n_kv=2, d_head=16, d_ff=128, vocab=256, window=8,
    local_global_period=4, rope_theta=10000.0, yarn=YARN,
    moe=MoECfg(n_experts=8, top_k=2, d_expert=32, held=HELD),
    tie_embeddings=False)

#: the same model as the reference module reads it (HF keys)
TINY_HF = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "sliding_window": 8,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000.0,
                           "factor": 4.0,
                           "original_max_position_embeddings": 16,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891}},
    "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
    "deployment": {"experts": 8, "experts_held": list(HELD)},
    "serving": {"kv_dtype": "bf16"}}


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "chip", "models", "mellum2.py")
    spec = importlib.util.spec_from_file_location("mellum2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def dims(ref):
    return ref.Dims(TINY_HF)


@pytest.fixture(scope="module")
def weights(ref, dims):
    return ref.make_weights(dims, 2**33 + 5)


def served_logits(params, requests, slots=4, chunk=4):
    """{rid: [logits of each emitted token]} of one session."""
    eng = Engine(TINY, params=params)
    sess = eng.session(batch_slots=slots, max_len=64, page_size=4,
                       kv_dtype="bf16", scheduler={"chunk": chunk})
    got = {}
    emit = sess._emit

    def emitted(i, logits_i, now):
        got.setdefault(sess.slot_entry[i].req.rid, []).append(
            np.array(logits_i))
        emit(i, logits_i, now)
    sess._emit = emitted
    for r in requests:
        sess.submit(r)
    results = {r.rid: r.tokens for r in sess.run()}
    return got, results, sess


# ----------------------------------------------------------- the layer
def plain_moe(p, x, n_experts, top_k):
    """The whole layer over every expert, in numpy float32."""
    x = np.asarray(x, np.float32).reshape(-1, x.shape[-1])
    lg = x @ np.asarray(p["router"], np.float32)
    pr = np.exp(lg - lg.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    top = np.argsort(-pr, axis=-1)[:, :top_k]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        w = pr[t, top[t]] / pr[t, top[t]].sum()
        for e, we in zip(top[t], w):
            g = x[t] @ np.asarray(p["gate"][e], np.float32)
            u = x[t] @ np.asarray(p["up"][e], np.float32)
            h = g / (1 + np.exp(-g)) * u
            out[t] += we * (h @ np.asarray(p["down"][e], np.float32))
    return out


def test_held_shares_add_up_to_the_uncut_layer():
    cfg = MoECfg(n_experts=8, top_k=2, d_expert=32)
    p = moe_mod.moe_init(jax.random.PRNGKey(3), 64, cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 64)) \
        .astype(jnp.bfloat16)
    whole, _, n_whole = moe_mod.moe_apply(p, x, cfg)
    parts, n_parts = [], 0
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        share = {"router": p["router"],
                 **{k: p[k][np.asarray(held)] for k in ("gate", "up",
                                                        "down")}}
        y, _, n = moe_mod.moe_apply(share, x,
                                    dataclasses.replace(cfg, held=held))
        parts.append(np.asarray(y, np.float32))
        n_parts += int(n)
    # every (token, expert) pair is computed on exactly one share
    assert n_parts == int(n_whole) == 2 * 6 * 2
    ref = plain_moe(p, x, 8, 2).reshape(2, 6, 64)
    scale = np.abs(ref).max()
    # two bf16 roundings of the output against one
    np.testing.assert_allclose(sum(parts), np.asarray(whole, np.float32),
                               atol=2e-2 * scale)
    # the layer is the uncut reference at bf16 compute
    np.testing.assert_allclose(sum(parts), ref, atol=3e-2 * scale)


def test_padded_rows_are_not_routed_computed_or_counted():
    cfg = MoECfg(n_experts=8, top_k=2, d_expert=32, held=(1, 3, 5, 7))
    p = moe_mod.moe_init(jax.random.PRNGKey(5), 64, cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 4, 64)) \
        .astype(jnp.bfloat16)
    valid = jnp.asarray([[1, 1, 1, 1], [1, 0, 0, 0], [0, 0, 0, 0]], bool)
    y, _, n = moe_mod.moe_apply(p, x, cfg, valid)
    y_all, _, _ = moe_mod.moe_apply(p, x, cfg)
    assert not np.asarray(y)[~np.asarray(valid)].any()
    np.testing.assert_array_equal(np.asarray(y)[np.asarray(valid)],
                                  np.asarray(y_all)[np.asarray(valid)])
    # the pairs counted are those of the five valid rows held here
    _, idx = jax.lax.top_k(jax.nn.softmax(
        x.reshape(12, 64).astype(jnp.float32) @ p["router"]), 2)
    here = np.isin(np.asarray(idx), cfg.held).reshape(3, 4, 2)
    assert int(n) == int(here[np.asarray(valid)].sum())


def test_training_updates_f32_expert_masters():
    """Training keeps f32 masters and moments for the expert stacks, so
    a first AdamW step (a relative change far below bf16's 2**-8 step)
    moves nearly every expert weight."""
    from repro.data.pipeline import PipelineConfig, make_batch
    from repro.train import trainer
    state = trainer.init_state(TINY, jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        TINY, PipelineConfig(seed=0, global_batch=2, seq_len=32), 0).items()}
    step = jax.jit(trainer.make_train_step(
        TINY, trainer.TrainConfig(remat="none")))
    new, _ = step(state, batch)
    for k in ("gate", "up", "down"):
        before = state.params["layers"]["moe"][k]
        after = new.params["layers"]["moe"][k]
        assert before.dtype == after.dtype == jnp.float32
        assert new.opt.m["layers"]["moe"][k].dtype == jnp.float32
        assert float(jnp.mean(before != after)) > 0.9


def test_session_holds_expert_stacks_in_bf16():
    eng = Engine(TINY)
    sess = eng.session(batch_slots=2, max_len=32, scheduler={"chunk": 4})
    for k in ("gate", "up", "down"):
        assert eng.params["layers"]["moe"][k].dtype == jnp.float32
        assert sess.params["layers"]["moe"][k].dtype == jnp.bfloat16
    assert sess.params["layers"]["moe"]["router"].dtype == jnp.float32


# ------------------------------------------------ the served model
def test_session_matches_reference_past_window_and_yarn(ref, dims,
                                                        weights):
    prompt = np.random.default_rng(1).integers(0, 256, 13).tolist()
    got, results, sess = served_logits(
        weights, [Request(prompt=prompt, max_new=16, rid=0)], slots=1)
    toks = results[0]
    seq = prompt + toks[:-1]                   # 28 positions, window 8
    full = ref.reference_logits(dims, weights, seq)
    read = np.arange(len(prompt) - 1, len(seq))
    served = np.stack(got[0])
    gap = np.abs(served - full[read]).max()
    assert gap < TOL_LOGITS, gap
    control = ref.reference_logits(dims, weights, seq, control=True)
    assert np.abs(control[read] - full[read]).max() > TOL_LOGITS
    # the counters: every valid token went through 4 expert layers, and
    # at most top_k of its pairs were computed here (one slot: no idle
    # rows; the chunk's padded rows are not counted)
    st = sess.stats
    assert st["moe_tokens"] == 4 * (len(prompt) + len(toks) - 1)
    assert st["moe_layer_calls"] == 4 * st["steps"]
    assert 0 < st["moe_pairs_here"] <= 2 * st["moe_tokens"]


def test_request_logits_do_not_depend_on_its_batch(weights):
    rng = np.random.default_rng(2)
    reqs = [Request(prompt=rng.integers(0, 256, 10).tolist(), max_new=8,
                    rid=i) for i in range(4)]
    alone, _, _ = served_logits(weights, reqs[:1])
    batch, _, _ = served_logits(weights, reqs)
    for a, b in zip(alone[0], batch[0]):
        np.testing.assert_array_equal(a, b)


def test_dense_config_counts_nothing_new():
    cfg = reduced(get("qwen1.5-0.5b"))
    sess = Engine(cfg).session(batch_slots=2, max_len=32,
                               scheduler={"chunk": 4})
    sess.submit(Request(prompt=[1, 2, 3, 4, 5], max_new=3))
    sess.run()
    assert not any(k.startswith("moe_") for k in sess.stats)


# ----------------------------------------------------- window semantics
def test_windowed_paged_attention_matches_plain_reference():
    """A query at q attends keys in (q - window, q]: decode and chunk,
    XLA and Pallas, GQA 8:1 at head 128, window 8 of 24 positions."""
    B, Hkv, G, Dh, ps, S, W, C = 2, 1, 8, 128, 4, 24, 8, 4
    rng = np.random.default_rng(9)
    npp = S // ps
    pool = kvs.init_pool(1 + B * npp, Hkv, ps, Dh, kv_dtype="bf16")
    table = jnp.asarray(1 + np.arange(B * npp).reshape(B, npp), jnp.int32)
    k = rng.normal(size=(B, Hkv, S, Dh)).astype(np.float32)
    v = rng.normal(size=(B, Hkv, S, Dh)).astype(np.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    pool = kvs.update_chunk(pool, table, jnp.asarray(k), jnp.asarray(v),
                            pos)
    kb = np.asarray(jnp.asarray(k, jnp.bfloat16), np.float32)
    vb = np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32)
    q = rng.normal(size=(B, Hkv * G, C, Dh)).astype(np.float32)
    q_pos = np.broadcast_to(np.arange(S - C, S)[None], (B, C))

    def plain(qi, at):                      # q [B, H, Dh] at position at
        s = np.einsum("bkgd,bkcd->bkgc", qi.reshape(B, Hkv, G, Dh),
                      kb) / np.sqrt(Dh)
        keys = np.arange(S)
        s = np.where((keys <= at) & (keys > at - W), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bkgc,bkcd->bkgd", p, vb).reshape(B, Hkv * G, Dh)

    want = np.stack([plain(q[:, :, c], S - C + c) for c in range(C)], 2)
    qj, pj = jnp.asarray(q), jnp.asarray(q_pos, jnp.int32)
    chunk = {"xla": kvs.paged_attention_xla_chunk(qj, pool, table, pj, W),
             "pallas": kvs.paged_attention_pallas_chunk(
                 qj, pool, table, pj, W, pb=2, qt=2, interpret=True)}
    last = jnp.full((B,), S - 1, jnp.int32)
    decode = {"xla": kvs.paged_attention(qj[:, :, -1], pool, table, last,
                                         W, impl="xla"),
              "pallas": kvs.paged_attention(qj[:, :, -1], pool, table,
                                            last, W, impl="pallas", pb=2,
                                            interpret=True)}
    # bf16 operands of the probabilities: 1e-2 of unit-scale values
    for name, o in chunk.items():
        np.testing.assert_allclose(np.asarray(o), want, atol=2e-2,
                                   err_msg=name)
    for name, o in decode.items():
        np.testing.assert_allclose(np.asarray(o), want[:, :, -1],
                                   atol=2e-2, err_msg=name)
    # a window one wider reads one more key: the boundary is load-bearing
    wider = kvs.paged_attention_xla_chunk(qj, pool, table, pj, W + 1)
    assert np.abs(np.asarray(wider) - want).max() > 0.05


# ------------------------------------------------------ qwen's RoPE
def _rope_before_tables(x, positions, theta=10000.0, table=None):
    """``layers.rope`` as it was before per-layer tables, verbatim."""
    assert table is None
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def test_qwen_logits_unchanged_by_rope_tables(monkeypatch):
    from repro.models import attention, layers
    cfg = dataclasses.replace(reduced(get("qwen1.5-0.5b")),
                              name="qwen1.5-rope-check")
    assert layers.rope_tables(cfg) is None
    params = Engine(cfg).params
    reqs = [Request(prompt=[3, 1, 4, 1, 5, 9, 2, 6, 5], max_new=6, rid=0)]

    def logits():
        eng = Engine(cfg, params=params)
        sess = eng.session(batch_slots=2, max_len=32, kv_dtype="bf16",
                           scheduler={"chunk": 4})
        got = []
        emit = sess._emit
        sess._emit = lambda i, lg, now: (got.append(np.array(lg)),
                                         emit(i, lg, now))
        for r in reqs:
            sess.submit(r)
        sess.run()
        return np.stack(got)

    now = logits()
    jax.clear_caches()
    monkeypatch.setattr(attention, "rope", _rope_before_tables)
    from repro.api import session
    from repro.sched import prefill
    monkeypatch.setattr(session, "_STEP_CACHE", {})
    monkeypatch.setattr(prefill, "_PREFILL_CACHE", {})
    before = logits()
    np.testing.assert_array_equal(now, before)
