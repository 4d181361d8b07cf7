"""The serving steps write the stacked KV pool in place: bf16 pages are
written whole at ``[layer, page]`` and the pool rides in the layer
scan's carry.  Against the form it replaced (a per-token scatter that
indexes the page and slot axes, with the pool scanned in and out layer
by layer, both kept here as the reference), every page but the garbage
page and every logits row come out bit-identical; int8 pools keep their
per-layer write and are compared the same way."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kvstore as kvs
from repro.configs import get, reduced
from repro.models import model as M
from repro.models import transformer as tfm
from repro.sched import prefill
from repro.sched.prefill import prefill_step

SLOTS, PAGE, CHUNK, MAX_LEN = 4, 4, 6, 32
NPP = MAX_LEN // PAGE
N_PAGES = 2 + SLOTS * NPP
SHARED = N_PAGES - 1    # shared by slots 0 and 1 as a full prefix page


def _cfg(arch):
    if arch == "mellum2-12b-ep4":
        # both layer kinds (3 windowed, 1 full), GQA 2:1
        return dataclasses.replace(reduced(get(arch), n_layers=4), n_kv=2)
    return reduced(get(arch))


# ----------------------------------------------------------- the reference
def _token_scatter(pool, table, k_new, v_new, positions, valid):
    """The bf16 write as it was: one scatter of single tokens indexing the
    page and slot axes, with the head axis as its window."""
    ps = pool.page_size
    npp = table.shape[1]
    pi = jnp.clip(positions // ps, 0, npp - 1)
    slot = positions % ps
    page = jnp.take_along_axis(table, pi, axis=1)
    if valid is not None:
        page = jnp.where(valid, page, kvs.NO_PAGE)
    safe = jnp.maximum(page, kvs.GARBAGE_PAGE)
    dt = pool.k_pages.dtype
    kf = k_new.astype(jnp.float32).transpose(0, 2, 1, 3).astype(dt)
    vf = v_new.astype(jnp.float32).transpose(0, 2, 1, 3).astype(dt)
    return kvs.PagedKV(pool.k_pages.at[safe, :, slot].set(kf),
                       pool.v_pages.at[safe, :, slot].set(vf))


def _ref_update_layer(pool, layer, table, k_new, v_new, positions, valid,
                      attend):
    """Layer ``layer`` sliced out, written by the old per-layer write,
    attended over, and put back."""
    view = kvs.layer_view(pool, layer)
    if not view.quantized:
        view = _token_scatter(view, table, k_new, v_new, positions, valid)
    elif k_new.shape[2] == 1:
        view = kvs.update(view, table, k_new[:, :, 0], v_new[:, :, 0],
                          positions[:, 0],
                          None if valid is None else valid[:, 0])
    else:
        view = kvs.update_chunk(view, table, k_new, v_new, positions, valid)
    out = attend(view, None)
    return jax.tree.map(
        lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, layer, 0),
        pool, view), out


def _ref_scan_layers(cfg, block, stacked, states, x, paged, unroll=False):
    """The layer scan as it was: every state leaf, the pool included,
    scanned in and out; each layer sees its own pool as a one-layer
    stack."""
    def one(kv):
        return jax.tree.map(lambda a: a[None], kv)

    def body(xc, inp):
        p, st, lx = inp
        if paged:
            st = {**st, "kv": one(st["kv"])}
        new_st, xo, pairs = block(p, st, xc, lx, 0 if paged else None)
        if paged:
            new_st = {**new_st,
                      "kv": jax.tree.map(lambda a: a[0], new_st["kv"])}
        return xo, (new_st, pairs)

    x, (new_states, pairs) = jax.lax.scan(
        body, x, (stacked, states, tfm.layer_xs(cfg)))
    return new_states, x, pairs


@pytest.fixture
def reference(monkeypatch):
    """Swap the in-place write and the carried scan for the old forms."""
    def use():
        monkeypatch.setattr(kvs, "update_layer", _ref_update_layer)
        monkeypatch.setattr(tfm, "scan_layers", _ref_scan_layers)
        monkeypatch.setattr(prefill, "scan_layers", _ref_scan_layers)
    return use


# ------------------------------------------------------------- scenarios
def _state(cfg, kv_dtype, table):
    state = M.init_decode_state(cfg, SLOTS, MAX_LEN, kv_cache="paged",
                                page_size=PAGE, kv_pool_pages=N_PAGES,
                                kv_dtype=kv_dtype)
    state["page_table"] = jnp.asarray(table, jnp.int32)
    # nonzero history everywhere, so a page written whole where a token
    # was due shows
    kv = state["layers"]["kv"]
    rng = np.random.default_rng(1)
    fill = {"k_pages": kv.k_pages, "v_pages": kv.v_pages}
    for name, a in fill.items():
        if kv.quantized:
            vals = rng.integers(-127, 128, a.shape)
        else:
            vals = rng.normal(size=a.shape)
        fill[name] = jnp.asarray(vals, a.dtype)
    if kv.quantized:
        fill["k_scale"] = jnp.full(kv.k_scale.shape, 0.05, jnp.float32)
        fill["v_scale"] = jnp.full(kv.v_scale.shape, 0.05, jnp.float32)
    state["layers"]["kv"] = kv._replace(**fill)
    return state


def _own_pages():
    """Slot i owns table index j as page 1 + i * NPP + j."""
    return 1 + np.arange(SLOTS * NPP).reshape(SLOTS, NPP)


# each: (table, start positions, calls: ("chunked", n_tok per slot) or
# ("decode", None), which writes every slot, a NO_PAGE row to the
# garbage page)
def _scenarios():
    own = _own_pages()
    no_page = own.copy()
    no_page[2, 1:] = kvs.NO_PAGE            # slot 2 runs into NO_PAGE
    no_page[3, :] = kvs.NO_PAGE             # slot 3 idle, no pages
    shared = own.copy()
    shared[0, 0] = shared[1, 0] = SHARED    # a full prefix page, shared
    return {
        # 3 + 6 crosses from page 0 into page 2; 2 + 6 ends on a boundary
        "page_boundary": (own, [3, 2, 7, 0], [("chunked", [6, 6, 5, 6]),
                                             ("decode", None),
                                             ("chunked", [6, 2, 6, 1])]),
        "idle_one_partial": (own, [0, 5, 9, 1],
                             [("chunked", [0, 1, 4, 6]),
                              ("chunked", [1, 0, 6, 3]),
                              ("decode", None)]),
        "no_page": (no_page, [0, 1, 2, 6], [("chunked", [5, 6, 6, 0]),
                                           ("decode", None),
                                           ("chunked", [6, 1, 6, 2])]),
        "shared_prefix": (shared, [PAGE, PAGE, 0, 3],
                          [("chunked", [6, 3, 6, 6]), ("decode", None),
                           ("chunked", [1, 6, 2, 0])]),
    }


def _run(cfg, params, state, calls, seed):
    """The served sequence: logits rows of every call and the last
    state."""
    rng = np.random.default_rng(seed)
    dec = jax.jit(functools.partial(M.decode_step, cfg))
    chk = jax.jit(functools.partial(prefill_step, cfg))
    rows = []
    for kind, arg in calls:
        if kind == "decode":
            tokens = jnp.asarray(rng.integers(1, cfg.vocab, SLOTS),
                                 jnp.int32)
            state, logits, *_ = dec(params, state, tokens)
        else:
            tokens = jnp.asarray(rng.integers(1, cfg.vocab,
                                              (SLOTS, CHUNK)), jnp.int32)
            state, logits, *_ = chk(params, state, tokens,
                                    jnp.asarray(arg, jnp.int32))
        rows.append(np.asarray(logits))
    return rows, state


def _assert_same_pools(got, want):
    """Every leaf bit-identical on every page but the garbage page."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:],
                                      np.asarray(b)[:, 1:])


def _check_against_reference(arch, case, kv_dtype, reference):
    cfg = _cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    table, start, calls = _scenarios()[case]
    state = _state(cfg, kv_dtype, table)
    state["pos"] = jnp.asarray(start, jnp.int32)
    before = jax.tree.map(np.asarray, state["layers"]["kv"])
    got_rows, got = _run(cfg, params, state, calls, seed=7)
    reference()
    state = _state(cfg, kv_dtype, table)
    state["pos"] = jnp.asarray(start, jnp.int32)
    want_rows, want = _run(cfg, params, state, calls, seed=7)
    # a slot that owns no page attends over masked keys alone, so its row
    # (never sampled) reads the garbage page; every other row must match
    owns = (np.asarray(table) >= 0).any(axis=1)
    for a, b in zip(got_rows, want_rows):
        np.testing.assert_array_equal(a[owns], b[owns])
    np.testing.assert_array_equal(np.asarray(got["pos"]),
                                  np.asarray(want["pos"]))
    _assert_same_pools(got["layers"]["kv"], want["layers"]["kv"])
    if case == "shared_prefix":
        # neither sharing slot writes the full prefix page
        for a, b in zip(jax.tree.leaves(got["layers"]["kv"]),
                        jax.tree.leaves(before)):
            np.testing.assert_array_equal(np.asarray(a)[:, SHARED],
                                          b[:, SHARED])


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("case", list(_scenarios()))
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mellum2-12b-ep4"])
def test_steps_match_the_token_scatter_and_scanned_pool(arch, case,
                                                        kv_dtype,
                                                        reference):
    _check_against_reference(arch, case, kv_dtype, reference)


@pytest.mark.parametrize("case", list(_scenarios()))
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mellum2-12b-ep4"])
def test_sliced_bf16_write_matches_the_reference(arch, case, reference,
                                                 monkeypatch):
    """The write a TPU takes for a bf16 pool held pages minor (a head
    narrower than the 128 lanes): each layer sliced out, written whole
    pages by ``_write_tokens``, attended over and put back.  Here on the
    CPU the dispatch would pick the in-place write, so it is pinned."""
    monkeypatch.setattr(kvs, "update_layer",
                        kvs.pool._update_layer_sliced)
    _check_against_reference(arch, case, "bf16", reference)


# -------------------------------------------------- the write on its own
@pytest.mark.parametrize("start,n_tok", [
    ([0, 3, 5, 7], [4, 4, 4, 4]),        # page-aligned, crossing, crossing
    ([1, 2, 6, 12], [0, 1, 3, 5]),       # idle, one token, partial
    ([28, 30, 31, 0], [4, 2, 1, 4]),     # the table's last page
])
def test_update_chunk_matches_the_token_scatter(start, n_tok):
    """update_chunk's whole-page bf16 write against the per-token scatter
    on one layer's pool, a NO_PAGE entry included."""
    hkv, dh, c = 2, 8, 4
    rng = np.random.default_rng(3)
    table = _own_pages().copy()
    table[1, 1] = kvs.NO_PAGE
    table = jnp.asarray(table, jnp.int32)
    pool = kvs.PagedKV(*(jnp.asarray(rng.normal(size=(N_PAGES, hkv, PAGE,
                                                      dh)), jnp.bfloat16)
                         for _ in range(2)))
    k, v = (jnp.asarray(rng.normal(size=(SLOTS, hkv, c, dh)), jnp.float32)
            for _ in range(2))
    positions = jnp.asarray(start, jnp.int32)[:, None] + jnp.arange(c)
    valid = jnp.arange(c)[None] < jnp.asarray(n_tok)[:, None]
    got = kvs.update_chunk(pool, table, k, v, positions, valid=valid)
    want = _token_scatter(pool, table, k, v, positions, valid)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a)[1:], np.asarray(b)[1:])


def test_update_matches_the_token_scatter():
    """update's one-token bf16 write, a row redirected by ``valid``."""
    hkv, dh = 2, 8
    rng = np.random.default_rng(4)
    table = jnp.asarray(_own_pages(), jnp.int32)
    pool = kvs.PagedKV(*(jnp.asarray(rng.normal(size=(N_PAGES, hkv, PAGE,
                                                      dh)), jnp.bfloat16)
                         for _ in range(2)))
    k, v = (jnp.asarray(rng.normal(size=(SLOTS, hkv, dh)), jnp.float32)
            for _ in range(2))
    pos = jnp.asarray([0, 5, 14, 31], jnp.int32)
    valid = jnp.asarray([True, True, False, True])
    got = kvs.update(pool, table, k, v, pos, valid=valid)
    want = _token_scatter(pool, table, k[:, :, None], v[:, :, None],
                          pos[:, None], valid[:, None])
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a)[1:], np.asarray(b)[1:])


# ------------------------------------------------------ the compiled steps
def _pool_relayouts(hlo: str, pool_shape) -> list:
    """``copy`` and ``transpose`` instructions (fused ones included)
    whose output is one layer's pool or the stacked pool, of any element
    type (the CPU computes bf16 updates in f32)."""
    layer = ",".join(map(str, pool_shape[1:]))
    shapes = {layer, "1," + layer, ",".join(map(str, pool_shape))}
    found = []
    for m in re.finditer(r"%(\S+) = \w+\[([\d,]*)\]\S* (copy|transpose)\(",
                         hlo):
        if m.group(2) in shapes:
            found.append(m.group(1))
    return found


@pytest.mark.parametrize("kind", ["decode", "chunked"])
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mellum2-12b-ep4"])
def test_compiled_steps_copy_no_pool(arch, kind):
    """Neither compiled step, its state donated, copies or transposes a
    layer's pool or the stacked pool.  Fails on the form this replaced:
    there the CPU compile transposes and copies each layer's pool around
    its token scatter and copies the stacked pool out of the scan."""
    cfg = _cfg(arch)
    params = jax.eval_shape(lambda: M.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: M.init_decode_state(
        cfg, SLOTS, MAX_LEN, kv_cache="paged", page_size=PAGE,
        kv_pool_pages=N_PAGES, kv_dtype="bf16"))
    if kind == "decode":
        fn = functools.partial(M.decode_step, cfg)
        args = (jax.ShapeDtypeStruct((SLOTS,), jnp.int32),)
    else:
        fn = functools.partial(prefill_step, cfg)
        args = (jax.ShapeDtypeStruct((SLOTS, CHUNK), jnp.int32),
                jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    hlo = jax.jit(fn, donate_argnums=(1,)).lower(
        params, state, *args).compile().as_text()
    assert _pool_relayouts(hlo, state["layers"]["kv"].k_pages.shape) == []


# ------------------------------------------ the invariant the writes need
@pytest.mark.parametrize("chunk", [1, CHUNK])
def test_no_two_slots_write_one_page(chunk):
    """Whole-page writes need what the Session guarantees: before every
    step, the pages that active slots' next tokens land in are each
    owned by that slot alone (refcount 1: never a shared prefix page)
    and no two slots' sets meet.  Driven through the prefix cache, with
    prompts that share three full pages."""
    from repro.api import Request
    from repro.api.session import Session
    cfg = _cfg("qwen1.5-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    sess = Session(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN,
                   page_size=PAGE,
                   scheduler={"chunk": chunk, "prefix_cache": True})
    head = list(range(1, 3 * PAGE + 1))
    for i in range(2 * SLOTS):
        sess.submit(Request(prompt=head + [40 + i] * (i % 4),
                            max_new=5, rid=i))
    ensure = sess._ensure_pages
    checked = []

    def checked_ensure(counts):
        granted = ensure(counts)
        seen = set()
        for i, n in enumerate(counts):
            if not n or sess.slot_entry[i] is None:
                continue
            lo = sess.slot_pos[i] // PAGE
            hi = min((sess.slot_pos[i] + n - 1) // PAGE, NPP - 1)
            pages = {int(p) for p in sess.host_table[i, lo:hi + 1]}
            assert all(sess.alloc.refcount(p) == 1 for p in pages)
            assert not pages & seen
            seen |= pages
        checked.append(len(seen))
        return granted

    sess._ensure_pages = checked_ensure
    assert len(sess.run()) == 2 * SLOTS
    assert sess.stats["prefix_pages_reused"] > 0
    assert max(checked) > 1          # steps with several writers checked
