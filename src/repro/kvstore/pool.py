"""Paged KV page pool — the AIDA memory model applied to the KV cache.

The dense decode cache materializes ``[B, Hkv, S_max, Dh]`` bf16 per layer
whether a sequence uses 3 tokens or 300.  The pool replaces that with a
shared set of fixed-size pages::

    k_pages / v_pages : [n_pages, Hkv, page_size, Dh]   int8 (or bf16)
    k_scale / v_scale : [n_pages, Hkv]                  f32 (int8 mode only)

plus one per-sequence *page table* ``[B, n_pages_per_seq] int32`` shared by
every layer (each layer owns its own pool arrays but sequence ``b`` uses
the same page ids at the same table index in all of them, so the
scan-over-layers stays homogeneous).  Token ``t`` of sequence ``b`` lives
at ``(page_table[b, t // page_size], t % page_size)`` — the table index IS
the absolute position, so attention masks need no stored positions.

Quantization follows the paper's precision lever (AIDA §IV): int8 codes
against a *per-page, per-head* f32 scale.  The scale is grown online —
when a new token's amax exceeds the page's current scale, the page's
existing codes are requantized against the new scale in the same fused
update (one page of traffic, ≤0.5 LSB added error per rescale).  Page 0
is reserved as a garbage sink: unallocated table entries (-1) clamp to it
so inactive batch slots can write unconditionally inside jit.

The serving steps hold every layer's pool stacked, ``[L, n_pages, Hkv,
page_size, Dh]``, and carry it through their layer scan: each layer
writes whole pages (:func:`update_layer`), in place where the chip holds
pages major, so no step copies the stack anew.

Bytes per token (k+v): int8 pages cost ``2·Hkv·Dh + 8·Hkv/page_size``
vs ``4·Hkv·Dh`` for the dense bf16 cache — ~0.50x at Dh=32, ps=16.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple, TypeVar

import jax
import jax.numpy as jnp

_T = TypeVar("_T")

#: table entry meaning "no page allocated here"
NO_PAGE = -1
#: page id reserved as the write sink for unallocated/inactive slots
GARBAGE_PAGE = 0


class PagedKV(NamedTuple):
    """One layer's share of the page pool (clean pytree: arrays only, or
    None scales in the unquantized bf16 mode — None leaves vanish from the
    tree so both modes scan/shard cleanly)."""
    k_pages: jnp.ndarray                   # [n_pages, Hkv, ps, Dh]
    v_pages: jnp.ndarray                   # [n_pages, Hkv, ps, Dh]
    k_scale: Optional[jnp.ndarray] = None  # [n_pages, Hkv] f32 (int8 mode)
    v_scale: Optional[jnp.ndarray] = None

    @property
    def page_size(self) -> int:
        return int(self.k_pages.shape[-2])

    @property
    def n_pages(self) -> int:
        return int(self.k_pages.shape[-4])

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_pool(n_pages: int, n_kv: int, page_size: int, d_head: int,
              kv_dtype: str = "int8") -> PagedKV:
    """A fresh pool. ``kv_dtype``: "int8" (quantized) or "bf16" (exact)."""
    if kv_dtype == "int8":
        shape = (n_pages, n_kv, page_size, d_head)
        return PagedKV(k_pages=jnp.zeros(shape, jnp.int8),
                       v_pages=jnp.zeros(shape, jnp.int8),
                       k_scale=jnp.zeros((n_pages, n_kv), jnp.float32),
                       v_scale=jnp.zeros((n_pages, n_kv), jnp.float32))
    if kv_dtype == "bf16":
        shape = (n_pages, n_kv, page_size, d_head)
        return PagedKV(k_pages=jnp.zeros(shape, jnp.bfloat16),
                       v_pages=jnp.zeros(shape, jnp.bfloat16))
    raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                     "choose 'int8' or 'bf16'")


def init_table(batch: int, max_len: int, page_size: int) -> jnp.ndarray:
    """Empty per-sequence page table [B, n_pages_per_seq]."""
    npp = -(-max_len // page_size)
    return jnp.full((batch, npp), NO_PAGE, jnp.int32)


def _quantize(new, s):
    """int8 codes of ``new`` [B, Hkv, Dh] against scales ``s`` [B, Hkv]."""
    codes = jnp.where(s[..., None] > 0,
                      new / jnp.maximum(s[..., None], 1e-30), 0.0)
    return jnp.clip(jnp.round(codes), -127, 127).astype(jnp.int8)


def _write_page_rescale(pages, scale, new, new_s, safe_page, slot):
    """Slow path: grow the per-page scale, requantize the page's existing
    codes against it, and write the new token's codes at ``slot``.
    Batched gather/scatter on the page index; the allocator guarantees
    active sequences never share a page, so scatter collisions only
    happen on the garbage page."""
    b = new.shape[0]
    ps = pages.shape[2]
    old_s = scale[safe_page]                              # [B, Hkv]
    # ratio <= 1; a fresh page has old_s == 0, so any stale codes are
    # wiped by ratio == 0
    ratio = jnp.where(new_s > 0, old_s / jnp.maximum(new_s, 1e-30), 0.0)
    pg = pages[safe_page].astype(jnp.float32)             # [B, Hkv, ps, Dh]
    pg = jnp.round(pg * ratio[..., None, None])
    hot = (jax.lax.broadcasted_iota(jnp.int32, (b, ps), 1)
           == slot[:, None])                              # [B, ps]
    pg = jnp.where(hot[:, None, :, None],
                   _quantize(new, new_s).astype(jnp.float32)[:, :, None, :],
                   pg)
    pages = pages.at[safe_page].set(pg.astype(jnp.int8))
    scale = scale.at[safe_page].set(new_s)
    return pages, scale


def _write_tokens(pool: PagedKV, table: jnp.ndarray, k_new: jnp.ndarray,
                  v_new: jnp.ndarray, start: jnp.ndarray,
                  valid: Optional[jnp.ndarray], layer=None) -> PagedKV:
    """bf16 write of a run of tokens, k/v [B, Hkv, C, Dh] at positions
    ``start[b] .. start[b] + C - 1`` (``valid`` [B, C] or None = all), a
    whole page at a time: gather the pages the run reaches, merge its
    tokens in, and scatter the pages back indexing the page axis alone
    (``[layer, page]`` in a stacked [L, n_pages, ...] pool).  A scatter
    that also indexed the slot axis would take the head axis as its
    window, and XLA then lays the whole pool out again around it.

    Every page other than the garbage page ends up as a per-token
    scatter would leave it.  Invalid tokens and ``NO_PAGE`` entries go
    to the garbage page, and so does a table index no valid token
    reaches, so one row never names a page twice; positions past the
    table write nothing.  Two rows never write the same real page: the
    allocator gives each active slot its own, and shared prefix pages
    are full and never written."""
    b, hkv, c, dh = k_new.shape
    ps = pool.page_size
    npp = table.shape[1]
    span = (c + ps - 2) // ps + 1              # pages C positions can reach
    first = start // ps                                     # [B]
    idx = first[:, None] + jnp.arange(span, dtype=jnp.int32)  # [B, span]
    # the chunk token each cell of the reached pages would take
    tok = (jnp.arange(span * ps, dtype=jnp.int32)[None]
           - (start % ps)[:, None])                         # [B, span*ps]
    lands = (tok >= 0) & (tok < c)
    tok = jnp.clip(tok, 0, c - 1)
    if valid is not None:
        lands &= jnp.take_along_axis(valid, tok, axis=1)
    lands = lands.reshape(b, span, ps)
    reached = lands.any(axis=-1) & (idx < npp)
    page = jnp.take_along_axis(table, jnp.minimum(idx, npp - 1), axis=1)
    ids = jnp.maximum(jnp.where(reached, page, NO_PAGE), GARBAGE_PAGE)
    at = ids if layer is None else (layer, ids)

    def write(pages, new):
        new = new.astype(pages.dtype).transpose(0, 2, 1, 3)  # [B, C, Hkv, Dh]
        new = jnp.take_along_axis(new, tok[:, :, None, None], axis=1)
        new = new.reshape(b, span, ps, hkv, dh).transpose(0, 1, 3, 2, 4)
        merged = jnp.where(lands[:, :, None, :, None], new, pages[at])
        return pages.at[at].set(merged)

    return PagedKV(write(pool.k_pages, k_new), write(pool.v_pages, v_new))


@jax.named_scope("kv.write")
def update(pool: PagedKV, table: jnp.ndarray, k_new: jnp.ndarray,
           v_new: jnp.ndarray, cur_pos: jnp.ndarray,
           valid: Optional[jnp.ndarray] = None) -> PagedKV:
    """Insert one token's k/v ([B, Hkv, Dh]) at absolute position
    ``cur_pos`` [B] through the page table.  Pure function of array
    inputs — safe inside the jitted, scanned decode step.

    ``valid`` [B] bool (optional) redirects invalid rows to the garbage
    sink — the chunked-prefill step uses it for the padding tail of a
    short chunk, so one fixed-width step serves mixed prefill+decode
    batches without conditional writes.

    int8 mode is two-speed: when every page's current scale already
    covers the new token (the steady state — scales grow only a handful
    of times per page), the write is a plain scatter of fresh codes; only
    a genuine scale growth pays the gather-requantize-scatter round trip
    (lax.cond, so the fast path skips the page traffic entirely).  bf16
    mode writes the token's whole page (:func:`_write_tokens`)."""
    if not pool.quantized:
        return _write_tokens(pool, table, k_new[:, :, None],
                             v_new[:, :, None], cur_pos,
                             None if valid is None else valid[:, None])
    ps = pool.page_size
    npp = table.shape[1]
    pi = jnp.clip(cur_pos // ps, 0, npp - 1)
    slot = cur_pos % ps
    page = table[jnp.arange(table.shape[0]), pi]          # [B]
    if valid is not None:
        page = jnp.where(valid, page, NO_PAGE)
    safe = jnp.maximum(page, GARBAGE_PAGE)                # -1 -> sink page
    kf = k_new.astype(jnp.float32)
    vf = v_new.astype(jnp.float32)
    k_amax = jnp.max(jnp.abs(kf), axis=-1) / 127.0        # [B, Hkv]
    v_amax = jnp.max(jnp.abs(vf), axis=-1) / 127.0
    if valid is not None:
        # a padded token must never grow a real page's scale
        k_amax = jnp.where(valid[:, None], k_amax, 0.0)
        v_amax = jnp.where(valid[:, None], v_amax, 0.0)
    old_ks = pool.k_scale[safe]
    old_vs = pool.v_scale[safe]
    new_ks = jnp.maximum(old_ks, k_amax)
    new_vs = jnp.maximum(old_vs, v_amax)
    grow = jnp.any((k_amax > old_ks) | (v_amax > old_vs))

    def fast(pool):
        kp = pool.k_pages.at[safe, :, slot].set(_quantize(kf, old_ks))
        vp = pool.v_pages.at[safe, :, slot].set(_quantize(vf, old_vs))
        return PagedKV(kp, vp, pool.k_scale, pool.v_scale)

    def slow(pool):
        kp, ks = _write_page_rescale(pool.k_pages, pool.k_scale, kf,
                                     new_ks, safe, slot)
        vp, vs = _write_page_rescale(pool.v_pages, pool.v_scale, vf,
                                     new_vs, safe, slot)
        return PagedKV(kp, vp, ks, vs)

    return jax.lax.cond(grow, slow, fast, pool)


@jax.named_scope("kv.write")
def update_chunk(pool: PagedKV, table: jnp.ndarray, k_new: jnp.ndarray,
                 v_new: jnp.ndarray, positions: jnp.ndarray,
                 valid: Optional[jnp.ndarray] = None) -> PagedKV:
    """Insert a whole chunk's k/v ([B, Hkv, C, Dh]) at absolute positions
    ``positions`` [B, C] through the page table — the multi-token
    generalization of :func:`update`, ONE scatter per chunk instead of a
    scan of C single-token writes (the chunked-prefill hot path).

    ``valid`` [B, C] bool redirects padding tokens to the garbage sink
    exactly like :func:`update`'s per-token flag.  bf16 pools take each
    row's positions as one run from ``positions[:, 0]`` (the chunk step
    feeds consecutive positions) and write the pages it reaches whole
    (:func:`_write_tokens`), bit-identical to the equivalent scan on
    every page but the garbage page.  int8 pools keep the two-speed
    semantics at chunk granularity: per-page scales grow to cover the
    chunk's max |amax| landing on each page (a segment-max scatter), and
    only a genuine growth pays the gather-requantize-scatter round trip
    — under one ``lax.cond`` for the whole chunk.  Chunk tokens are
    quantized directly against the final page scale, so a chunk write
    never pays the intra-chunk rescale random walk the scan did (error
    stays within the same ~1 LSB bound, from above)."""
    if not pool.quantized:
        return _write_tokens(pool, table, k_new, v_new, positions[:, 0],
                             valid)
    ps = pool.page_size
    npp = table.shape[1]
    pi = jnp.clip(positions // ps, 0, npp - 1)            # [B, C]
    slot = positions % ps
    page = jnp.take_along_axis(table, pi, axis=1)         # [B, C]
    if valid is not None:
        page = jnp.where(valid, page, NO_PAGE)
    safe = jnp.maximum(page, GARBAGE_PAGE)
    # token-major layout: [B, C, Hkv, Dh] matches the scatter index shape
    kf = k_new.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v_new.astype(jnp.float32).transpose(0, 2, 1, 3)
    k_amax = jnp.max(jnp.abs(kf), axis=-1) / 127.0        # [B, C, Hkv]
    v_amax = jnp.max(jnp.abs(vf), axis=-1) / 127.0
    if valid is not None:
        # a padded token must never grow a real page's scale
        k_amax = jnp.where(valid[..., None], k_amax, 0.0)
        v_amax = jnp.where(valid[..., None], v_amax, 0.0)
    old_ks = pool.k_scale[safe]                           # [B, C, Hkv]
    old_vs = pool.v_scale[safe]
    # final per-page scale: old scale vs the chunk's per-page amax peak
    # (segment max over however many chunk tokens land on each page —
    # elementwise-max scatter, so duplicate page ids are well-defined)
    new_ks_full = pool.k_scale.at[safe].max(k_amax)       # [n_pages, Hkv]
    new_vs_full = pool.v_scale.at[safe].max(v_amax)
    new_ks = new_ks_full[safe]                            # [B, C, Hkv]
    new_vs = new_vs_full[safe]
    grow = jnp.any((k_amax > old_ks) | (v_amax > old_vs))

    def _quant_tok(xf, s):
        codes = jnp.where(s[..., None] > 0,
                          xf / jnp.maximum(s[..., None], 1e-30), 0.0)
        return jnp.clip(jnp.round(codes), -127, 127).astype(jnp.int8)

    def fast(pool):
        kp = pool.k_pages.at[safe, :, slot].set(_quant_tok(kf, old_ks))
        vp = pool.v_pages.at[safe, :, slot].set(_quant_tok(vf, old_vs))
        return PagedKV(kp, vp, pool.k_scale, pool.v_scale)

    def _rescale_pages(pages, old_s, new_s, xf):
        # 1) requantize each WRITTEN page's existing codes old -> new
        #    scale.  ratio is a page-level value gathered per token, so
        #    duplicate page ids scatter identical full-page content —
        #    order-independent by construction.
        ratio = jnp.where(new_s > 0,
                          old_s / jnp.maximum(new_s, 1e-30), 0.0)
        pg = pages[safe].astype(jnp.float32)          # [B, C, Hkv, ps, Dh]
        pg = jnp.round(pg * ratio[..., None, None])
        pages = pages.at[safe].set(pg.astype(jnp.int8))
        # 2) land the chunk's codes, quantized against the final scale
        #    (distinct (page, slot) cells for every valid token)
        return pages.at[safe, :, slot].set(_quant_tok(xf, new_s))

    def slow(pool):
        kp = _rescale_pages(pool.k_pages, old_ks, new_ks, kf)
        vp = _rescale_pages(pool.v_pages, old_vs, new_vs, vf)
        return PagedKV(kp, vp, new_ks_full, new_vs_full)

    return jax.lax.cond(grow, slow, fast, pool)


def layer_view(pool: PagedKV, layer) -> PagedKV:
    """Layer ``layer`` of a stacked [L, n_pages, ...] pool."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        pool)


def _pages_minor_on_tpu(pool: PagedKV) -> bool:
    """Whether a TPU holds this pool with its pages in the lanes: with a
    head that is not a whole number of the 128 lanes, any order that
    keeps a page's rows together pads each row to 128, so the chip's
    default order puts the page axis minor-most, the order that pads
    least.  No page can be gathered or scattered where it lies then."""
    return pool.k_pages.shape[-1] % 128 != 0


def _update_layer_sliced(pool: PagedKV, layer, table, k_new, v_new,
                         positions, valid, attend):
    """:func:`update_layer` on a slice: layer ``layer`` is sliced out,
    written there (:func:`update` for one token, :func:`update_chunk` for
    a chunk), read there and put back."""
    with jax.named_scope("kv.write"):
        view = layer_view(pool, layer)
    if k_new.shape[2] == 1:
        view = update(view, table, k_new[:, :, 0], v_new[:, :, 0],
                      positions[:, 0], None if valid is None else valid[:, 0])
    else:
        view = update_chunk(view, table, k_new, v_new, positions, valid)
    out = attend(view, None)
    with jax.named_scope("kv.write"):
        pool = jax.tree.map(
            lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, layer, 0),
            pool, view)
    return pool, out


def _update_layer_in_place(pool: PagedKV, layer, table, k_new, v_new,
                           positions, valid, attend):
    """:func:`update_layer` in place: whole bf16 pages written at
    ``[layer, page]`` of the stack, and attention gathers the layer's
    pages from the stack."""
    with jax.named_scope("kv.write"):
        pool = _write_tokens(pool, table, k_new, v_new, positions[:, 0],
                             valid, layer)
    return pool, attend(pool, layer)


def update_layer(pool: PagedKV, layer, table: jnp.ndarray,
                 k_new: jnp.ndarray, v_new: jnp.ndarray,
                 positions: jnp.ndarray, valid: Optional[jnp.ndarray],
                 attend: Callable[[PagedKV, Optional[jnp.ndarray]], _T]
                 ) -> Tuple[PagedKV, _T]:
    """Write a run of tokens, k/v [B, Hkv, C, Dh] at contiguous
    positions [B, C] (C = 1 for a decode token), into layer ``layer`` of
    the stacked pool [L, n_pages, Hkv, ps, Dh] that the serving steps
    carry through their layer scan, then attend over that layer.
    Returns (pool', ``attend(src, at)``): ``attend`` reads layer ``at``
    of the stacked pool ``src``, or with ``at`` None the one layer's
    pool ``src`` (``paged_attention(..., layer=at)``).

    A bf16 pool held pages major is written in place
    (:func:`_update_layer_in_place`), so no step copies a layer or the
    stack.  Where a TPU holds the pool pages minor
    (:func:`_pages_minor_on_tpu`; compiled for a v5e at qwen1.5-0.5b
    widths, writing in place there lays the whole stack out pages major
    and back, 6 copies of it a step), and for an int8 pool (two-speed
    write unchanged), each layer is written on a slice
    (:func:`_update_layer_sliced`)."""
    args = (layer, table, k_new, v_new, positions, valid, attend)
    if pool.quantized:
        return _update_layer_sliced(pool, *args)
    if not _pages_minor_on_tpu(pool):
        return _update_layer_in_place(pool, *args)
    return jax.lax.platform_dependent(
        pool, tpu=lambda p: _update_layer_sliced(p, *args),
        default=lambda p: _update_layer_in_place(p, *args))


def gather_kv(pool: PagedKV, table: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize per-sequence K/V from the pool (XLA reference path):
    [B, npp] table -> dequantized ([B, Hkv, npp*ps, Dh] f32) k, v."""
    b, npp = table.shape
    _, hkv, ps, dh = pool.k_pages.shape
    safe = jnp.maximum(table, GARBAGE_PAGE)
    k = jnp.take(pool.k_pages, safe, axis=0)   # [B, npp, Hkv, ps, Dh]
    v = jnp.take(pool.v_pages, safe, axis=0)
    if pool.quantized:
        ks = jnp.take(pool.k_scale, safe, axis=0)         # [B, npp, Hkv]
        vs = jnp.take(pool.v_scale, safe, axis=0)
        k = k.astype(jnp.float32) * ks[..., None, None]
        v = v.astype(jnp.float32) * vs[..., None, None]
    else:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, hkv, npp * ps, dh)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, hkv, npp * ps, dh)
    return k, v


def chunk_attention_mask(table: jnp.ndarray, q_pos: jnp.ndarray,
                         window: jnp.ndarray,
                         page_size: int) -> jnp.ndarray:
    """[B, C, npp*ps] bool: positions each of C queries (at absolute
    positions ``q_pos`` [B, C]) may attend to; table index is absolute
    position, and window < 0 means full causal.  Every key position <= a
    query's position has been written by the time the query attends
    (writes happen first, in position order), so plain causality over
    table-index positions is sufficient."""
    b, npp = table.shape
    pos = jnp.arange(npp * page_size)[None, None, :]      # [1, 1, npp*ps]
    alloc = jnp.repeat(table >= 0, page_size,
                       axis=1)[:, None, :]                # [B, 1, npp*ps]
    ok = alloc & (pos <= q_pos[:, :, None])
    win_lo = jnp.where(window < 0, jnp.int32(-1),
                       q_pos[:, :, None] - window)
    return ok & (pos > win_lo)


# ---------------------------------------------------------- cross-pool copy
def _page_axis(leaf) -> int:
    """Page axis of a pool leaf: 0 for a single layer's [n_pages, ...]
    arrays, 1 for the scan-stacked [L, n_pages, ...] serving layout."""
    return leaf.ndim - 4 if leaf.ndim >= 4 else leaf.ndim - 2


def _same_devices(a, b) -> bool:
    sa, sb = getattr(a, "sharding", None), getattr(b, "sharding", None)
    if sa is None or sb is None:
        return True
    return sa.device_set == sb.device_set


def copy_pages(src: PagedKV, dst: PagedKV, src_ids, dst_ids,
               dst_shardings: Optional[PagedKV] = None
               ) -> Tuple[PagedKV, int]:
    """Copy pages ``src_ids`` of ``src`` into pages ``dst_ids`` of ``dst``
    (another pool of the same geometry) and return ``(new_dst, bytes)``.

    The payload moves verbatim: bf16 pages are bit-exact, int8 pages move
    codes *and* per-page scales with no requantization (zero added error).
    Works on single-layer pools and the scan-stacked [L, n_pages, ...]
    serving layout alike.  When the two pools live on different device
    sets (disaggregated roles on disjoint mesh subsets) the payload is
    staged through the host; same-device copies stay on device.
    ``dst_shardings`` (a PagedKV of NamedShardings) re-commits the updated
    leaves so a jitted step with explicit in_shardings sees no surprise
    placement."""
    if src.page_size != dst.page_size or \
            src.k_pages.shape[-2:] != dst.k_pages.shape[-2:] or \
            src.quantized != dst.quantized:
        raise ValueError(
            f"pool geometry mismatch: src {src.k_pages.shape} "
            f"({src.k_pages.dtype}) vs dst {dst.k_pages.shape} "
            f"({dst.k_pages.dtype})")
    si = jnp.asarray(src_ids, jnp.int32)
    di = jnp.asarray(dst_ids, jnp.int32)
    if si.shape != di.shape:
        raise ValueError(f"{si.shape[0]} source pages for "
                         f"{di.shape[0]} destinations")
    moved = 0

    def copy_leaf(s, d, sh):
        nonlocal moved
        if s is None:
            return None
        ax = _page_axis(s)
        block = jnp.take(s, si, axis=ax)
        moved += block.size * block.dtype.itemsize
        if not _same_devices(s, d):
            block = jnp.asarray(jax.device_get(block))
        idx = (slice(None),) * ax + (di,)
        out = d.at[idx].set(block.astype(d.dtype))
        if sh is not None:
            out = jax.device_put(out, sh)
        return out

    shs = dst_shardings or PagedKV(None, None, None, None)
    if si.shape[0] == 0:
        return dst, 0
    return PagedKV(
        k_pages=copy_leaf(src.k_pages, dst.k_pages, shs.k_pages),
        v_pages=copy_leaf(src.v_pages, dst.v_pages, shs.v_pages),
        k_scale=copy_leaf(src.k_scale, dst.k_scale, shs.k_scale),
        v_scale=copy_leaf(src.v_scale, dst.v_scale, shs.v_scale)), moved


# ------------------------------------------------------------- accounting
def kv_bytes_per_token(n_kv: int, d_head: int, page_size: int,
                       kv_dtype: str = "int8") -> float:
    """Steady-state pool bytes per cached token (k+v, scales amortized)."""
    if kv_dtype == "int8":
        return 2 * n_kv * d_head + 2 * n_kv * 4 / page_size
    return 2 * n_kv * d_head * 2          # bf16 pages


def dense_kv_bytes_per_token(n_kv: int, d_head: int) -> float:
    """The dense bf16 cache burns this per *slot* whether used or not."""
    return 2 * n_kv * d_head * 2
