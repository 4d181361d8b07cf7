"""Paged, quantized KV-cache subsystem: pool/allocator semantics, paged
attention (Pallas vs XLA vs dense reference), decode equivalence against
the full cache, session lifecycle (alloc on boundary, free on completion,
SWA reclamation), and int8 error bounds.

Key invariants:
  * bf16 pages reproduce the full bf16 cache BIT-EXACTLY through the
    decode step (same mixed-precision semantics, page-gathered);
  * int8 pages stay inside the quantization floor (~1 LSB of the
    per-page scale after online requantization) and well under 0.55x
    the dense cache's bytes per token;
  * pages never leak: every alloc is matched by a free at request
    completion / slot reset / SWA reclamation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kvstore as kvs
from repro.api import Engine, Request
from repro.configs import get, reduced
from repro.models import model as M

CFG = reduced(get("llama3-8b"), n_layers=2, d_model=64, d_ff=128, vocab=256)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(0))


def dense_attention_ref(q, k, v, scale, window=-1):
    """numpy oracle: full-precision masked GQA attention over history."""
    b, h, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)
    sc = np.einsum("bkgd,bkcd->bkgc", qg, k) * scale
    if window >= 0:
        pos = np.arange(s)
        sc = np.where(pos[None, None, None] > s - 1 - window, sc, -1e30)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bkgc,bkcd->bkgd", p, v).reshape(b, h, dh)


def fill_pool(rng, B, Hkv, Dh, ps, npp, S, kv_dtype="int8", scramble=None):
    """Write S tokens through update(); page ids optionally scrambled."""
    pool = kvs.init_pool(1 + B * npp, Hkv, ps, Dh, kv_dtype=kv_dtype)
    table = np.full((B, npp), -1, np.int32)
    alloc = kvs.PageAllocator(pool.n_pages)
    order = list(range(1, pool.n_pages))
    if scramble is not None:
        scramble.shuffle(order)
    nxt = iter(order)
    ks = rng.normal(size=(S, B, Hkv, Dh)).astype(np.float32)
    vs = rng.normal(size=(S, B, Hkv, Dh)).astype(np.float32)
    for t in range(S):
        for b in range(B):
            if table[b, t // ps] < 0:
                pid = next(nxt)
                alloc._free.remove(pid)
                alloc._used.add(pid)
                table[b, t // ps] = pid
        pool = kvs.update(pool, jnp.asarray(table), jnp.asarray(ks[t]),
                          jnp.asarray(vs[t]), jnp.full((B,), t, jnp.int32))
    return pool, jnp.asarray(table), ks, vs


# ------------------------------------------------------------- allocator
def test_allocator_randomized_orderings():
    rng = np.random.default_rng(0)
    a = kvs.PageAllocator(32)
    held = []
    for _ in range(2000):
        if held and rng.random() < 0.45:
            k = rng.integers(1, len(held) + 1)
            batch = [held.pop(rng.integers(len(held))) for _ in range(k)]
            a.free(batch)
        elif a.available:
            pid = a.alloc()
            assert pid != kvs.GARBAGE_PAGE
            assert pid not in held          # never handed out twice
            held.append(pid)
        assert a.in_use == len(held)
    a.free(held)
    assert a.in_use == 0 and a.available == 31
    a.free(held)                            # double-free is a no-op
    assert a.available == 31


def test_allocator_exhaustion_raises():
    a = kvs.PageAllocator(4)
    got = [a.alloc() for _ in range(3)]
    assert sorted(got) == [1, 2, 3]
    with pytest.raises(kvs.OutOfPages):
        a.alloc()
    assert a.peak == 3


def test_reclaimable_prefix():
    # window 5, ps 4: positions < cur-window+1 are dead
    assert kvs.reclaimable_prefix(3, 5, 4) == 0
    assert kvs.reclaimable_prefix(8, 5, 4) == 1      # pos 0..3 dead at cur=8
    assert kvs.reclaimable_prefix(12, 5, 4) == 2
    assert kvs.reclaimable_prefix(100, -1, 4) == 0   # global: never
    assert kvs.reclaimable_prefix(100, 0, 4) == 0


# ----------------------------------------------------- pool + attention
@pytest.mark.parametrize("kv_dtype,tol", [("bf16", 1.2e-2), ("int8", 6e-2)])
def test_paged_attention_vs_dense_reference(kv_dtype, tol):
    B, Hkv, G, Dh, ps, npp, S = 2, 2, 2, 16, 4, 3, 9
    rng = np.random.default_rng(0)
    pool, table, ks, vs = fill_pool(rng, B, Hkv, Dh, ps, npp, S, kv_dtype,
                                    scramble=np.random.default_rng(7))
    q = rng.normal(size=(B, Hkv * G, Dh)).astype(np.float32)
    cur = jnp.full((B,), S - 1, jnp.int32)
    o = np.asarray(kvs.paged_attention_xla(jnp.asarray(q), pool, table,
                                           cur, -1))
    ref = dense_attention_ref(q, ks.transpose(1, 2, 0, 3),
                              vs.transpose(1, 2, 0, 3), Dh ** -0.5)
    np.testing.assert_allclose(o, ref, atol=tol)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("pb", [1, 2, 3])
@pytest.mark.parametrize("window", [-1, 5])
def test_pallas_kernel_matches_xla(kv_dtype, pb, window):
    B, Hkv, G, Dh, ps, npp, S = 2, 2, 2, 16, 4, 3, 10
    rng = np.random.default_rng(1)
    pool, table, _, _ = fill_pool(rng, B, Hkv, Dh, ps, npp, S, kv_dtype)
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, Dh)), jnp.float32)
    cur = jnp.full((B,), S - 1, jnp.int32)
    o_x = kvs.paged_attention_xla(q, pool, table, cur, window)
    o_p = kvs.paged_attention_pallas(q, pool, table, cur, window, pb=pb,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p),
                               atol=2e-2 if kv_dtype == "bf16" else 1e-5,
                               rtol=2e-2 if kv_dtype == "bf16" else 1e-5)


def test_pallas_softcap_and_unallocated_pages():
    B, Hkv, G, Dh, ps, npp, S = 1, 2, 1, 16, 4, 4, 6   # 2 pages unused
    rng = np.random.default_rng(2)
    pool, table, _, _ = fill_pool(rng, B, Hkv, Dh, ps, npp, S, "int8")
    assert int((np.asarray(table) >= 0).sum()) == 2    # -1 tail masked
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, Dh)), jnp.float32)
    cur = jnp.full((B,), S - 1, jnp.int32)
    o_x = kvs.paged_attention_xla(q, pool, table, cur, -1, cap=20.0)
    o_p = kvs.paged_attention_pallas(q, pool, table, cur, -1, cap=20.0,
                                     pb=2, interpret=True)
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p), atol=1e-5)


# -------------------------------------------------- chunked prefill path
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_update_chunk_matches_scan_of_updates(kv_dtype):
    """update_chunk (one scatter per chunk) == a scan of per-token
    update() calls: bf16 bit-identical; int8 lands the same dequantized
    values within the documented ~1 LSB bound (the chunk write quantizes
    against the final page scale instead of walking per-token rescales,
    so codes may differ by a rounding step but content may not)."""
    B, Hkv, Dh, ps, npp, S, C = 2, 2, 8, 4, 5, 9, 4
    rng = np.random.default_rng(5)
    pool, table, _, _ = fill_pool(rng, B, Hkv, Dh, ps, npp, S, kv_dtype)
    kc = jnp.asarray(rng.normal(size=(B, Hkv, C, Dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, Hkv, C, Dh)), jnp.float32)
    positions = jnp.broadcast_to(
        jnp.arange(S, S + C, dtype=jnp.int32)[None], (B, C))
    valid = jnp.asarray([[True] * C, [True, True, False, False]])
    scanned = pool
    for j in range(C):
        scanned = kvs.update(scanned, table, kc[:, :, j], vc[:, :, j],
                             positions[:, j], valid=valid[:, j])
    vec = kvs.update_chunk(pool, table, kc, vc, positions, valid=valid)
    # page 0 is the garbage sink: scatter collisions land there by design
    # (invalid/overflow tokens), its content is documented don't-care —
    # compare real pages only
    if kv_dtype == "bf16":
        for a, b in ((scanned.k_pages, vec.k_pages),
                     (scanned.v_pages, vec.v_pages)):
            np.testing.assert_array_equal(np.asarray(a)[1:],
                                          np.asarray(b)[1:])
    else:
        for pages_a, pages_b, sc_a, sc_b in (
                (scanned.k_pages, vec.k_pages, scanned.k_scale,
                 vec.k_scale),
                (scanned.v_pages, vec.v_pages, scanned.v_scale,
                 vec.v_scale)):
            da = np.asarray(pages_a, np.float32) * \
                np.asarray(sc_a)[:, :, None, None]
            db = np.asarray(pages_b, np.float32) * \
                np.asarray(sc_b)[:, :, None, None]
            bound = 2.0 * np.maximum(np.asarray(sc_a),
                                     np.asarray(sc_b))[:, :, None, None]
            assert (np.abs(da - db) <= bound + 1e-7)[1:].all()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("pb,qt", [(1, 1), (2, 2), (3, 4), (2, None)])
@pytest.mark.parametrize("window", [-1, 5])
def test_chunk_pallas_matches_xla(kv_dtype, pb, qt, window):
    """Pallas chunk kernel == XLA chunk reference across page-block and
    query-tile candidates (same tolerances as the decode kernel test:
    online softmax vs. one-shot softmax rounding in bf16)."""
    B, Hkv, G, Dh, ps, npp, S, C = 2, 2, 2, 16, 4, 3, 10, 4
    rng = np.random.default_rng(6)
    pool, table, _, _ = fill_pool(rng, B, Hkv, Dh, ps, npp, S, kv_dtype)
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, C, Dh)), jnp.float32)
    q_pos = jnp.broadcast_to(
        jnp.arange(S - C, S, dtype=jnp.int32)[None], (B, C))
    o_x = kvs.paged_attention_xla_chunk(q, pool, table, q_pos, window,
                                        cap=20.0)
    o_p = kvs.paged_attention_pallas_chunk(q, pool, table, q_pos, window,
                                           cap=20.0, pb=pb, qt=qt,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p),
                               atol=2e-2 if kv_dtype == "bf16" else 1e-5,
                               rtol=2e-2 if kv_dtype == "bf16" else 1e-5)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_chunk_c1_bit_identical_to_decode_kernel(kv_dtype):
    """Pallas decode is a one-query chunk of the chunk kernel: its
    [B, H, Dh] entry point matches a C=1 chunk bit for bit."""
    B, Hkv, G, Dh, ps, npp, S = 2, 2, 3, 16, 4, 3, 10
    rng = np.random.default_rng(7)
    pool, table, _, _ = fill_pool(rng, B, Hkv, Dh, ps, npp, S, kv_dtype)
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, Dh)), jnp.float32)
    cur = jnp.full((B,), S - 1, jnp.int32)
    for pb, window in ((1, -1), (2, 5), (4, -1)):
        o_d = kvs.paged_attention_pallas(q, pool, table, cur, window,
                                         pb=pb, interpret=True)
        o_c = kvs.paged_attention_pallas_chunk(
            q[:, :, None], pool, table, cur[:, None], window, pb=pb,
            qt=1, interpret=True)
        np.testing.assert_array_equal(np.asarray(o_d),
                                      np.asarray(o_c[:, :, 0]))


def test_chunk_dispatch_and_bucketed_key():
    """paged_attention_chunk honors a pinned impl, and the tune key
    buckets npp so a growing table maps to one cache entry."""
    from repro.kernels import tune
    B, Hkv, G, Dh, ps, npp, S, C = 1, 2, 2, 8, 4, 3, 8, 2
    rng = np.random.default_rng(8)
    pool, table, _, _ = fill_pool(rng, B, Hkv, Dh, ps, npp, S, "bf16")
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, C, Dh)), jnp.float32)
    q_pos = jnp.broadcast_to(
        jnp.arange(S - C, S, dtype=jnp.int32)[None], (B, C))
    o_auto = kvs.paged_attention_chunk(q, pool, table, q_pos, -1,
                                       interpret=True)
    o_xla = kvs.paged_attention_chunk(q, pool, table, q_pos, -1,
                                      impl="xla", interpret=True)
    np.testing.assert_allclose(np.asarray(o_auto), np.asarray(o_xla),
                               atol=2e-2, rtol=2e-2)
    # npp 5..8 bucket to one key; 9 starts the next bucket
    keys = {tune.paged_key(2, 2, 8, 4, n, 1, False, True)
            for n in (5, 6, 7, 8)}
    assert len(keys) == 1
    assert tune.paged_key(2, 2, 8, 4, 9, 1, False, True) not in keys
    ckeys = {tune.paged_chunk_key(2, 2, 8, 4, n, 1, C, False, True)
             for n in (5, 6, 7, 8)}
    assert len(ckeys) == 1


def test_int8_error_bound():
    """Online requantization stays inside ~1 LSB of the final per-page
    scale (0.5 LSB base + the rescale random walk).  Dequantizes through
    gather_kv — the naive per-sequence materialization oracle — so the
    table-order position convention is asserted along the way."""
    B, Hkv, Dh, ps, npp, S = 1, 4, 32, 8, 5, 40
    rng = np.random.default_rng(0)
    pool, table, ks, vs = fill_pool(rng, B, Hkv, Dh, ps, npp, S, "int8")
    deq_k, deq_v = (np.asarray(x) for x in kvs.gather_kv(pool, table))
    sc = np.asarray(pool.k_scale)
    tbl = np.asarray(table)
    for t in range(S):
        pid = tbl[0, t // ps]
        err = np.abs(deq_k[0, :, t] - ks[t, 0])          # [Hkv, Dh]
        assert (err <= 2.0 * sc[pid][:, None] + 1e-7).all()
        errv = np.abs(deq_v[0, :, t] - vs[t, 0])
        assert (errv <= 2.0 * np.asarray(
            pool.v_scale)[pid][:, None] + 1e-7).all()


def test_bytes_per_token_budget():
    pbt = kvs.kv_bytes_per_token(CFG.n_kv, CFG.head_dim, 16, "int8")
    dbt = kvs.dense_kv_bytes_per_token(CFG.n_kv, CFG.head_dim)
    assert pbt / dbt <= 0.55


# --------------------------------------------------- decode equivalence
def test_paged_bf16_decode_is_bit_exact(params):
    """bf16 pages through the real decode step == the full bf16 cache,
    bit for bit (same mixed-precision semantics, page-gathered)."""
    toks = [1, 7, 3, 9, 2, 8, 4, 6, 5] * 3
    step = jax.jit(lambda p, s, t: M.decode_step(CFG, p, s, t))

    def logits_for(state):
        out = []
        for t in toks:
            state, lg = step(params, state, jnp.asarray([t]))
            out.append(np.asarray(lg[0, :CFG.vocab]))
        return np.stack(out)

    full = logits_for(M.init_decode_state(CFG, 1, 32))
    st = M.init_decode_state(CFG, 1, 32, kv_cache="paged", page_size=8,
                             kv_dtype="bf16")
    npp = st["page_table"].shape[1]
    st["page_table"] = jnp.asarray(np.arange(1, npp + 1)[None], jnp.int32)
    paged = logits_for(st)
    np.testing.assert_array_equal(full, paged)


def test_paged_int8_decode_logits_close(params):
    """int8 pages track the full bf16 cache within the quantization
    floor (~1 LSB of the KV scales, measured ~0.11 peak on random-init
    logits of scale ~4; the bound is the regression tripwire — bf16
    pages cover exactness above).  Random-init logits are near-uniform,
    so a few greedy flips at ~zero margin are expected and benign."""
    toks = [1, 7, 3, 9, 2, 8, 4, 6, 5] * 3
    step = jax.jit(lambda p, s, t: M.decode_step(CFG, p, s, t))

    def logits_for(state):
        out = []
        for t in toks:
            state, lg = step(params, state, jnp.asarray([t]))
            out.append(np.asarray(lg[0, :CFG.vocab]))
        return np.stack(out)

    full = logits_for(M.init_decode_state(CFG, 1, 32))
    st = M.init_decode_state(CFG, 1, 32, kv_cache="paged", page_size=8,
                             kv_dtype="int8")
    npp = st["page_table"].shape[1]
    st["page_table"] = jnp.asarray(np.arange(1, npp + 1)[None], jnp.int32)
    paged = logits_for(st)
    assert np.abs(full - paged).max() <= 0.2
    assert (full.argmax(-1) == paged.argmax(-1)).mean() >= 0.8


# -------------------------------------------------------------- session
def test_session_paged_matches_full_serving(params):
    """Refill-heavy continuous batch: identical greedy tokens through
    both cache kinds (bf16 pages — bit-exact attention), and no leaked
    pages afterwards."""
    reqs = lambda: [Request(prompt=[1, 2 + r], max_new=3 + 2 * r, rid=r)  # noqa: E731
                    for r in range(5)]
    eng = Engine(CFG, params=params)
    full = eng.serve(reqs(), batch_slots=2, max_len=32)
    sess = eng.session(batch_slots=2, max_len=32, kv_cache="paged",
                       page_size=8, kv_dtype="bf16")
    for r in reqs():
        sess.submit(r)
    paged = sess.run()
    assert [r.tokens for r in full] == [r.tokens for r in paged]
    assert sess.alloc.in_use == 0
    assert sess.stats["fills"] == 5
    assert sess.stats["page_allocs"] >= 5    # one page minimum per request


def test_session_randomized_alloc_free(params):
    """Random request lengths/order: every request completes, pages are
    recycled (peak stays below the worst case), nothing leaks."""
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=[1 + int(rng.integers(0, 40))] *
                    int(rng.integers(1, 6)),
                    max_new=int(rng.integers(1, 12)), rid=i)
            for i in range(9)]
    eng = Engine(CFG, params=params)
    sess = eng.session(batch_slots=3, max_len=32, kv_cache="paged",
                       page_size=4)
    for r in reqs:
        sess.submit(r)
    res = sess.run()
    assert [r.rid for r in res] == list(range(9))
    assert [len(r.tokens) for r in res] == [r.max_new for r in reqs]
    assert sess.alloc.in_use == 0
    assert sess.stats["pages_peak"] <= 3 * (32 // 4)


def test_session_out_of_pages_raises(params):
    eng = Engine(CFG, params=params)
    sess = eng.session(batch_slots=2, max_len=32, kv_cache="paged",
                       page_size=4, kv_pool_pages=3)   # 2 usable pages
    sess.submit(Request(prompt=[1, 2, 3, 4, 5], max_new=8, rid=0))
    with pytest.raises(kvs.OutOfPages):
        sess.run()
    # the failed allocation round rolled back: every allocator-held page
    # is visible in the host table (no orphaned grants)
    assert sess.alloc.in_use == int((sess.host_table >= 0).sum())


def test_swa_reclamation_over_page_boundaries():
    """Pure-SWA arch (danube): paged serving matches the dense ring cache
    token-for-token while pages behind the window are recycled, keeping
    residency O(window) — page-granular, across page boundaries."""
    cfg = reduced(get("h2o-danube-1.8b"))       # window 32, all layers
    eng = Engine(cfg)
    req = lambda: [Request(prompt=[1, 2, 3], max_new=56, rid=0)]  # noqa: E731
    full = eng.serve(req(), batch_slots=1, max_len=80)
    sess = eng.session(batch_slots=1, max_len=80, kv_cache="paged",
                       page_size=8, kv_dtype="bf16")
    for r in req():
        sess.submit(r)
    paged = sess.run()
    assert full[0].tokens == paged[0].tokens
    assert sess.stats["pages_reclaimed_swa"] > 0
    # live pages never exceed window/page_size + 2 boundary pages
    assert sess.stats["pages_peak"] <= 32 // 8 + 2


def test_paged_state_specs_match_state(params):
    """Sharding specs tree mirrors the paged decode state structure —
    for both pool dtypes (bf16 pools have None scale leaves)."""
    for dt in ("int8", "bf16"):
        st = M.init_decode_state(CFG, 2, 32, kv_cache="paged",
                                 page_size=8, kv_dtype=dt)
        sp = M.state_specs(CFG, 2, dp_ok=True, kv_cache="paged",
                           kv_dtype=dt)
        jax.tree.map(lambda a, b: None, st, sp)  # same treedef or raises


# ----------------------------------------------------- property sweeps
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYP = False

if HAVE_HYP:
    @settings(max_examples=15, deadline=None)
    @given(ps=st.sampled_from([2, 4, 8, 16]), S=st.integers(1, 24),
           B=st.integers(1, 3), window=st.sampled_from([-1, 3, 7]),
           seed=st.integers(0, 99))
    def test_prop_paged_attention(ps, S, B, window, seed):
        """(page_size, S, B) sweep: bf16 paged attention == windowed
        dense reference for any geometry, including part-filled pages."""
        Hkv, G, Dh = 2, 2, 8
        npp = max(1, -(-S // ps))
        rng = np.random.default_rng(seed)
        pool, table, ks, vs = fill_pool(
            rng, B, Hkv, Dh, ps, npp, S, "bf16",
            scramble=np.random.default_rng(seed + 1))
        q = rng.normal(size=(B, Hkv * G, Dh)).astype(np.float32)
        cur = jnp.full((B,), S - 1, jnp.int32)
        o = np.asarray(kvs.paged_attention_xla(jnp.asarray(q), pool,
                                               table, cur, window))
        ref = dense_attention_ref(q, ks.transpose(1, 2, 0, 3),
                                  vs.transpose(1, 2, 0, 3), Dh ** -0.5,
                                  window=window)
        np.testing.assert_allclose(o, ref, atol=2e-2)

    @settings(max_examples=12, deadline=None)
    @given(ps=st.sampled_from([2, 4, 8]), S=st.integers(2, 20),
           c=st.integers(1, 6), g=st.sampled_from([1, 2, 4]),
           window=st.sampled_from([-1, 3, 7]),
           cap=st.sampled_from([None, 15.0]),
           kv_dtype=st.sampled_from(["bf16", "int8"]),
           pb=st.sampled_from([1, 2, 4]), seed=st.integers(0, 99))
    def test_prop_chunk_pallas_matches_xla(ps, S, c, g, window, cap,
                                           kv_dtype, pb, seed):
        """(C, page_size, npp, GQA group, window, softcap) sweep: the
        Pallas chunk kernel tracks the XLA chunk reference for any
        geometry — part-filled pages, bucket-padded tables, in-chunk
        causality — at the decode-kernel tolerances (bf16 rounding from
        online vs. one-shot softmax; int8 contracts in f32 either way)."""
        c = min(c, S)
        Hkv, Dh = 2, 8
        npp = max(1, -(-S // ps))
        B = 2
        rng = np.random.default_rng(seed)
        pool, table, _, _ = fill_pool(
            rng, B, Hkv, Dh, ps, npp, S, kv_dtype,
            scramble=np.random.default_rng(seed + 1))
        q = jnp.asarray(rng.normal(size=(B, Hkv * g, c, Dh)), jnp.float32)
        q_pos = jnp.broadcast_to(
            jnp.arange(S - c, S, dtype=jnp.int32)[None], (B, c))
        o_x = kvs.paged_attention_xla_chunk(q, pool, table, q_pos, window,
                                            cap=cap)
        o_p = kvs.paged_attention_pallas_chunk(
            q, pool, table, q_pos, window, cap=cap, pb=pb,
            qt=2 if c % 2 == 0 else None, interpret=True)
        np.testing.assert_allclose(
            np.asarray(o_x), np.asarray(o_p),
            atol=2e-2 if kv_dtype == "bf16" else 1e-5,
            rtol=2e-2 if kv_dtype == "bf16" else 1e-5)
