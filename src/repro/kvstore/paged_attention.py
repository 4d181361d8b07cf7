"""Paged-attention decode kernel: page-table gather + inline dequant +
flash-style online softmax, one grid step per block of pages.

The Pallas kernel uses the canonical TPU paged-attention trick: the page
table rides in as a *scalar-prefetch* argument, so the K/V BlockSpec
index maps can read it and DMA exactly the pages a sequence owns —
``index_map=(table[b, i·pb+j], h, 0, 0)`` — no dense [B, S, ...] tensor
ever exists.  Each grid step covers ``pb`` table slots (pb separate
BlockSpecs per operand; a tuner-searchable tile), dequantizes them
against their per-page scales in VMEM, and folds them into the running
(m, l, acc) online-softmax state; the output block is finalized on the
last page block, exactly like kernels/flash_attention.py.

One kernel serves both shapes: the chunked-prefill kernel
(:func:`paged_attention_pallas_chunk`) takes a whole chunk
(``[B, H, C, Dh]``) at absolute positions ``q_pos``, with a ``qt``-query
tile folded into the online-softmax state per grid step and the
in-chunk causal mask (table-index position vs. per-query absolute
position) computed in-kernel; decode (:func:`paged_attention_pallas`,
``[B, H, Dh]``) is a one-query chunk.  Its blocks are laid out for the
TPU's (8, 128) tiling: the query tile is ``[qt·G, Dh]`` query-major
rows, and an int8 page's per-head scales ride as one ``[1, Hkv]`` row.

The XLA paths (`impl="xla"`) are the same math as gather + masked
softmax — the correctness oracle, the autodiff-free reference, and (on
interpret-mode hosts and on a v5e at serving sizes) the faster choice;
`paged_attention()` and `paged_attention_chunk()` dispatch per the
kernels.tune cache like the FC ops do.  Page tables are padded to an `npp_bucket` multiple of the
largest tuner `pb` so a growing table reuses one compiled kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import interpret_mode
from repro.kvstore import pool as poolmod
from repro.kvstore.pool import PagedKV

NEG_INF = -1e30

# Largest page-block candidate the tuner searches.  Page tables are
# padded (and tune keys bucketed) to the next PB_MAX multiple so a table
# that grows 17 -> 18 -> ... pages hits one compiled kernel + one tune
# entry instead of recompiling per npp.
PB_MAX = 4


def npp_bucket(npp: int) -> int:
    """Round a page-table width up to the next PB_MAX multiple."""
    return -(-npp // PB_MAX) * PB_MAX


def _softcap(s, cap: Optional[float]):
    return s if cap is None else cap * jnp.tanh(s / cap)


# ------------------------------------------------------------------- xla
def paged_attention_xla(q: jnp.ndarray, pool: PagedKV, table: jnp.ndarray,
                        cur_pos: jnp.ndarray, window, *,
                        scale: Optional[float] = None,
                        cap: Optional[float] = None) -> jnp.ndarray:
    """Reference path: q [B, H, Dh] against the paged pool -> [B, H, Dh]
    f32 — the chunk reference with one query per sequence at
    ``cur_pos``."""
    o = paged_attention_xla_chunk(
        q[:, :, None], pool, table, jnp.asarray(cur_pos)[:, None], window,
        scale=scale, cap=cap)
    return o[:, :, 0]


def paged_attention_xla_chunk(q: jnp.ndarray, pool: PagedKV,
                              table: jnp.ndarray, q_pos: jnp.ndarray,
                              window, *, scale: Optional[float] = None,
                              cap: Optional[float] = None) -> jnp.ndarray:
    """Multi-query reference for the chunked-prefill step: q [B, H, C, Dh]
    at absolute positions ``q_pos`` [B, C] against the paged pool ->
    [B, H, C, Dh] f32.  GQA by grouping query heads (no k/v repeat),
    masks from table-index positions — mirrors models.attention._core
    over gathered pages.  Chunk tokens see each other through the pool
    because their K/V are written before the chunk attends."""
    b, h, c, dh = q.shape
    _, hkv, ps, _ = pool.k_pages.shape
    g = h // hkv
    npp = table.shape[1]
    scale = (dh ** -0.5) if scale is None else scale
    safe = jnp.maximum(table, poolmod.GARBAGE_PAGE)
    with jax.named_scope("kv.read"):
        k = jnp.take(pool.k_pages, safe, axis=0)   # [B, P, Hkv, ps, Dh]
        v = jnp.take(pool.v_pages, safe, axis=0)
    # unquantized pages mirror _core's mixed precision (bf16 operands,
    # f32 accumulate/softmax) so paged bf16 == full cache up to reduction
    # order; int8 pages contract in f32 (dequant headroom)
    cdt = jnp.float32 if pool.quantized else k.dtype
    qg = q.reshape(b, hkv, g, c, dh).astype(cdt)
    # page axes stay in the einsum (no transposed [B,Hkv,S,Dh] copy); the
    # per-page dequant scales fold into the [.., p, c] score/prob tensors
    # instead of elementwise-dequantizing whole pages (Dh x less work)
    s = jnp.einsum("bkgqd,bpkcd->bkgqpc", qg, k.astype(cdt),
                   preferred_element_type=jnp.float32) * scale
    if pool.quantized:
        with jax.named_scope("kv.read"):
            ks = jnp.take(pool.k_scale, safe, axis=0)   # [B, P, Hkv]
        s = s * ks.transpose(0, 2, 1)[:, :, None, None, :, None]
    s = _softcap(s, cap)
    mask = poolmod.chunk_attention_mask(
        table, q_pos, jnp.asarray(window, jnp.int32),
        pool.page_size).reshape(b, c, npp, ps)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s.reshape(b, hkv, g, c, npp * ps), axis=-1)
    p = p.reshape(b, hkv, g, c, npp, ps)
    if pool.quantized:
        with jax.named_scope("kv.read"):
            vs = jnp.take(pool.v_scale, safe, axis=0)
        p = p * vs.transpose(0, 2, 1)[:, :, None, None, :, None]
    o = jnp.einsum("bkgqpc,bpkcd->bkgqd", p.astype(cdt), v.astype(cdt),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, c, dh)


# ---------------------------------------------------------------- pallas
def _head_scale(scale_ref, hi):
    """[1, 1] scale of kv-head ``hi`` from a page's [1, 1, Hkv] row."""
    row = scale_ref[0]                                     # [1, Hkv]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.where(lane == hi, row, 0.0).sum(axis=1, keepdims=True)


def _paged_chunk_kernel(table_ref, pos_ref, win_ref, q_ref, *refs,
                        scale, cap, quantized, pb, ps, nblk, qt, g):
    """One grid step = ``pb`` pages × a ``qt``-query tile of one
    (sequence, kv-head) folded into the online softmax.  Rows are the
    query tile's [qt, G] (query-major) block; each row's causal and
    window limits come from its query's absolute position.  refs order:
    k_0..k_{pb-1}, v_0..v_{pb-1}, [ks_0.., vs_0..], o_ref, m/l/acc
    scratch."""
    refs = list(refs)
    k_refs = [refs.pop(0) for _ in range(pb)]
    v_refs = [refs.pop(0) for _ in range(pb)]
    if quantized:
        ks_refs = [refs.pop(0) for _ in range(pb)]
        vs_refs = [refs.pop(0) for _ in range(pb)]
    o_ref, m_scr, l_scr, acc_scr = refs
    bi, hi = pl.program_id(0), pl.program_id(1)
    qi, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # bf16 pages keep bf16 MXU operands with f32 accumulation, as the XLA
    # path does; int8 pages dequantize and contract in f32
    cdt = jnp.float32 if quantized else k_refs[0].dtype
    q = q_ref[0, 0].astype(cdt)                            # [qt*G, Dh]
    win = win_ref[0]
    # per-row query position (row r is query r // G of the tile); masks
    # are built from int32 vectors by broadcasting, never by joining
    # boolean vectors, which the TPU cannot relayout
    row = jax.lax.broadcasted_iota(jnp.int32, (qt * g, 1), 0)
    cur = jnp.zeros((qt * g, 1), jnp.int32)
    for ti in range(qt):
        cur = jnp.where((row >= ti * g) & (row < (ti + 1) * g),
                        pos_ref[bi, qi * qt + ti], cur)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, pb * ps), 1)
    owned = jnp.zeros((1, pb * ps), jnp.int32)             # page allocated
    ks, vs = [], []
    for j in range(pb):                                    # static unroll
        t = i * pb + j                                     # table index
        kj = k_refs[j][0, 0].astype(cdt)                   # [ps, Dh]
        vj = v_refs[j][0, 0].astype(cdt)
        if quantized:                                      # per-page scale
            kj = kj * _head_scale(ks_refs[j], hi)
            vj = vj * _head_scale(vs_refs[j], hi)
        ks.append(kj)
        vs.append(vj)
        owned = jnp.where((lane >= j * ps) & (lane < (j + 1) * ps),
                          (table_ref[bi, t] >= 0).astype(jnp.int32), owned)
    k = jnp.concatenate(ks, axis=0)                        # [pb*ps, Dh]
    v = jnp.concatenate(vs, axis=0)
    base = i * (pb * ps) + lane                            # key positions
    mask = (owned > 0) & (base <= cur)
    mask &= (win < 0) | (base > cur - win)                 # [qt*G, pb*ps]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _softcap(s, cap)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(cdt), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(i == nblk - 1)
    def _done():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30))[None, None]


def legal_query_tile(qt: int, chunk: int, group: int) -> bool:
    """A query tile the kernel can run natively: it divides the chunk, and
    its [qt*G, Dh] block is a whole number of 8-row sublane tiles or the
    whole chunk."""
    return chunk % qt == 0 and ((qt * group) % 8 == 0 or qt == chunk)


@functools.partial(jax.jit, static_argnames=("scale", "cap", "pb", "qt",
                                             "interpret"))
def paged_attention_pallas_chunk(q, pool: PagedKV, table, q_pos, window, *,
                                 scale: Optional[float] = None,
                                 cap: Optional[float] = None,
                                 pb: int = 2, qt: Optional[int] = None,
                                 interpret: Optional[bool] = None):
    """Pallas chunked-prefill paged attention.  q [B, H, C, Dh] at
    absolute positions ``q_pos`` [B, C] -> [B, H, C, Dh] f32.

    Grid (B, Hkv, C/qt, nblk): each step DMAs ``pb`` pages straight from
    the table (scalar prefetch) and folds them into the [qt·G]-row
    online-softmax state — the chunk never materializes a dense
    [B, P, Hkv, ps, Dh] gather.  ``qt`` must divide C (falls back to a single C-wide
    tile otherwise).  ``interpret=None`` lowers natively on a TPU only."""
    b, h, c, dh = q.shape
    n_pages, hkv, ps, _ = pool.k_pages.shape
    g = h // hkv
    npp = table.shape[1]
    scale = (dh ** -0.5) if scale is None else scale
    npp_b = npp_bucket(npp)
    pb = max(1, min(pb, npp_b))
    nblk = -(-npp_b // pb)
    if nblk * pb != npp:   # pad table; -1 entries are masked in-kernel
        table = jnp.pad(table, ((0, 0), (0, nblk * pb - npp)),
                        constant_values=poolmod.NO_PAGE)
    qt = c if qt is None or c % qt != 0 else qt
    nq = c // qt
    # query-major rows: tile qi is the contiguous row range
    # [qi*qt*G, (qi+1)*qt*G) of [B, Hkv, C*G, Dh]
    qg = q.reshape(b, hkv, g, c, dh).transpose(0, 1, 3, 2, 4) \
        .reshape(b, hkv, c * g, dh)
    quantized = pool.quantized

    def page_map(j):
        return lambda bi, hi, qi, i, tbl, pos, win: (
            jnp.maximum(tbl[bi, i * pb + j], 0), hi, 0, 0)

    def scale_map(j):
        return lambda bi, hi, qi, i, tbl, pos, win: (
            jnp.maximum(tbl[bi, i * pb + j], 0), 0, 0)

    in_specs = [pl.BlockSpec((1, 1, qt * g, dh),
                             lambda bi, hi, qi, i, tbl, pos, win:
                             (bi, hi, qi, 0))]
    args = [qg]
    for pages in (pool.k_pages, pool.v_pages):
        for j in range(pb):
            in_specs.append(pl.BlockSpec((1, 1, ps, dh), page_map(j)))
            args.append(pages)
    if quantized:
        # a page's scales ride as one [1, 1, Hkv] row: the smallest block
        # of a [n_pages, Hkv] table the TPU's tiling admits
        for scales in (pool.k_scale, pool.v_scale):
            for j in range(pb):
                in_specs.append(pl.BlockSpec((1, 1, hkv), scale_map(j)))
                args.append(scales.reshape(n_pages, 1, hkv))
    kern = functools.partial(_paged_chunk_kernel, scale=scale, cap=cap,
                             quantized=quantized, pb=pb, ps=ps, nblk=nblk,
                             qt=qt, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, nq, nblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, qt * g, dh),
                               lambda bi, hi, qi, i, tbl, pos, win:
                               (bi, hi, qi, 0)),
        scratch_shapes=[pltpu.VMEM((qt * g, 1), jnp.float32),
                        pltpu.VMEM((qt * g, 1), jnp.float32),
                        pltpu.VMEM((qt * g, dh), jnp.float32)],
    )
    o = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, dh), jnp.float32),
        interpret=interpret_mode(interpret),
    )(table, jnp.asarray(q_pos, jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1), *args)
    return o.reshape(b, hkv, c, g, dh).transpose(0, 1, 3, 2, 4) \
        .reshape(b, h, c, dh)


def paged_attention_pallas(q, pool: PagedKV, table, cur_pos, window, *,
                           scale: Optional[float] = None,
                           cap: Optional[float] = None,
                           pb: int = 2, interpret: Optional[bool] = None):
    """Pallas paged decode attention. q [B, H, Dh] -> [B, H, Dh] f32: the
    chunk kernel with one query per sequence at ``cur_pos``."""
    o = paged_attention_pallas_chunk(
        q[:, :, None], pool, table,
        jnp.asarray(cur_pos, jnp.int32)[:, None], window, scale=scale,
        cap=cap, pb=pb, qt=1, interpret=interpret)
    return o[:, :, 0]


# ------------------------------------------------------------- dispatch
def resolve_paged(batch: int, h: int, d_head: int, pool: PagedKV,
                  npp: int, interpret: Optional[bool] = None):
    """Resolve the tuned decode choice -> (impl, pb, interpret).

    Pure host-side cache lookup, so shard_map wrappers can resolve with
    the *global* geometry outside the mesh and pass (impl, pb) in
    explicitly — mesh and single-device runs then execute the identical
    kernel (same accumulation order, token-identical output)."""
    from repro.kernels import tune as _tune
    interp = interpret_mode(interpret)
    hkv = pool.k_pages.shape[1]
    choice = _tune.get(_tune.paged_key(hkv, h // hkv, d_head,
                                       pool.page_size, npp, batch,
                                       pool.quantized, interp))
    if choice is not None:
        return choice.impl, (choice.tile("pb") or 2), interp
    # untuned default: native kernel on TPU, XLA on interpret hosts
    return ("xla" if interp else "pallas"), 2, interp


def resolve_paged_chunk(batch: int, h: int, chunk: int, d_head: int,
                        pool: PagedKV, npp: int,
                        interpret: Optional[bool] = None):
    """Resolve the tuned chunk choice -> (impl, pb, qt, interpret)."""
    from repro.kernels import tune as _tune
    interp = interpret_mode(interpret)
    hkv = pool.k_pages.shape[1]
    choice = _tune.get(_tune.paged_chunk_key(hkv, h // hkv, d_head,
                                             pool.page_size, npp, batch,
                                             chunk, pool.quantized, interp))
    if choice is not None:
        return (choice.impl, (choice.tile("pb") or 2),
                (choice.tile("qt") or chunk), interp)
    return ("xla" if interp else "pallas"), 2, chunk, interp


@jax.named_scope("attention")
def paged_attention(q, pool: PagedKV, table, cur_pos, window, *,
                    scale: Optional[float] = None,
                    cap: Optional[float] = None,
                    impl: Optional[str] = None,
                    pb: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Autotuned entry point: Pallas kernel or the XLA gather reference
    per the kernels.tune winner for this (geometry, batch, backend).
    Pass ``impl``/``pb`` to pin a choice (the shard wrappers do, with the
    globally-resolved one)."""
    if impl is None:
        b, h, dh = q.shape
        impl, pb, interpret = resolve_paged(b, h, dh, pool,
                                            table.shape[1], interpret)
    else:
        interpret = interpret_mode(interpret)
    if impl == "xla":
        return paged_attention_xla(q, pool, table, cur_pos, window,
                                   scale=scale, cap=cap)
    return paged_attention_pallas(q, pool, table, cur_pos, window,
                                  scale=scale, cap=cap,
                                  pb=pb or 2, interpret=interpret)


@jax.named_scope("attention")
def paged_attention_chunk(q, pool: PagedKV, table, q_pos, window, *,
                          scale: Optional[float] = None,
                          cap: Optional[float] = None,
                          impl: Optional[str] = None,
                          pb: Optional[int] = None,
                          qt: Optional[int] = None,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Autotuned chunked-prefill entry point: q [B, H, C, Dh] at absolute
    positions ``q_pos`` [B, C] -> [B, H, C, Dh].  Dispatches between
    :func:`paged_attention_pallas_chunk` and the XLA gather reference per
    the kernels.tune winner for this (geometry, batch, chunk, backend)."""
    if impl is None:
        b, h, c, dh = q.shape
        impl, pb, qt, interpret = resolve_paged_chunk(
            b, h, c, dh, pool, table.shape[1], interpret)
    else:
        interpret = interpret_mode(interpret)
    if impl == "xla":
        return paged_attention_xla_chunk(q, pool, table, q_pos, window,
                                         scale=scale, cap=cap)
    return paged_attention_pallas_chunk(q, pool, table, q_pos, window,
                                        scale=scale, cap=cap, pb=pb or 2,
                                        qt=qt, interpret=interpret)
