"""GQA attention with every variant the assigned archs need.

Per-layer attention windows are passed as *traced* scalars (-1 = full
causal), so a scanned layer stack can alternate local/global (gemma2,
hymba) without breaking layer-structure homogeneity.  The training/prefill
path defaults to the fused-mask jnp formulation (GSPMD-shardable, used by
the dry-run); `impl="flash"` switches to the Pallas kernels when the window
is static.  Decode attends against a KVCache (full or ring).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import kvstore as kvs
from repro.kernels import ops
from repro.models import kvcache as kvc
from repro.models.layers import COMPUTE_DTYPE, dense, dense_init, rope, softcap

NEG_INF = -1e30


def attn_init(key, d: int, n_heads: int, n_kv: int, d_head: int,
              qkv_bias: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, n_heads * d_head),
        "wk": dense_init(ks[1], d, n_kv * d_head),
        "wv": dense_init(ks[2], d, n_kv * d_head),
        "wo": dense_init(ks[3], n_heads * d_head, d),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv * d_head,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv * d_head,), jnp.float32)
    return p


def _split_heads(x, n, d_head):
    b, t, _ = x.shape
    return x.reshape(b, t, n, d_head).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _qkv(p, x, n_heads, n_kv, d_head, positions, theta, plan=None):
    q = dense(x, p["wq"], p.get("bq"), plan=plan)
    k = dense(x, p["wk"], p.get("bk"), plan=plan)
    v = dense(x, p["wv"], p.get("bv"), plan=plan)
    q = _split_heads(q, n_heads, d_head)
    k = _split_heads(k, n_kv, d_head)
    v = _split_heads(v, n_kv, d_head)
    if theta is not None:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    return q, k, v


def _core(q, k, v, mask, cap: Optional[float], scale: float):
    """Masked softmax attention (GSPMD-friendly einsum form).

    GQA is expressed by GROUPING query heads [B, Hkv, G, T, D] instead of
    jnp.repeat-ing k/v — the repeated [B,H,T,D] tensors never exist
    (§Perf: decode HBM bytes / flops ↓ for every GQA arch)."""
    b, h, tq, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, tq, d)
    s = jnp.einsum("bkgqd,bkcd->bkgqc", qg.astype(COMPUTE_DTYPE),
                   k.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap)
    if mask.ndim == 4:  # [B,1,Tq,Tk] or [1,1,Tq,Tk] -> group broadcast
        mask = mask[:, :, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(COMPUTE_DTYPE),
                   v.astype(COMPUTE_DTYPE))
    return o.reshape(b, h, tq, d)


def _chunked_core(q, k, v, window, causal, cap, scale, chunk: int,
                  unroll: bool = False):
    """Blockwise attention: scan over QUERY chunks — O(T·chunk) residency
    instead of O(T²), so long-sequence training fits HBM (flash-attention
    schedule expressed in XLA ops; the Pallas kernel is the TPU-fused
    version of the same schedule)."""
    b, h, t, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    chunk = min(chunk, t)
    assert t % chunk == 0
    qg = q.reshape(b, hkv, g, t, d).astype(COMPUTE_DTYPE)
    kc = k.astype(COMPUTE_DTYPE)
    vc = v.astype(COMPUTE_DTYPE)
    ki = jnp.arange(t)

    def one_chunk(ci):
        qs = jax.lax.dynamic_slice_in_dim(qg, ci * chunk, chunk, axis=3)
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qs, kc,
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, cap)
        qi = ci * chunk + jnp.arange(chunk)
        m = jnp.ones((chunk, t), jnp.bool_)
        if causal:
            m &= ki[None, :] <= qi[:, None]
        m &= (window < 0) | (ki[None, :] > qi[:, None] - window)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(COMPUTE_DTYPE), vc)

    n = t // chunk
    _, out = jax.lax.scan(lambda c, ci: (c, one_chunk(ci)), (),
                          jnp.arange(n), unroll=n if unroll else 1)
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, g, t, d)
    return out.reshape(b, h, t, d)


def attn_apply(p, x, positions, *, n_heads: int, n_kv: int, d_head: int,
               window, causal: bool = True, cap: Optional[float] = None,
               theta: Optional[float] = 10000.0,
               scale: Optional[float] = None, impl: str = "einsum",
               chunk: int = 512, unroll: bool = False):
    """Training / prefill self-attention.  window: traced scalar, -1=full."""
    scale = (d_head ** -0.5) if scale is None else scale
    q, k, v = _qkv(p, x, n_heads, n_kv, d_head, positions, theta)
    t = x.shape[1]
    if impl == "flash":
        win = None if (isinstance(window, int) and window < 0) else int(window)
        o = ops.attention(q, k, v, causal=causal, window=win, softcap=cap,
                          scale=scale, impl="flash")
    elif impl == "chunked":
        o = _chunked_core(q, k, v, window, causal, cap, scale, chunk,
                          unroll=unroll)
    else:
        qi = jnp.arange(t)[:, None]
        ki = jnp.arange(t)[None, :]
        mask = jnp.ones((t, t), jnp.bool_)
        if causal:
            mask &= ki <= qi
        wmask = (window < 0) | (ki > qi - window)
        mask = mask & wmask
        o = _core(q, k, v, mask[None, None], cap, scale)
    return dense(_merge_heads(o.astype(COMPUTE_DTYPE)), p["wo"])


def decode_attend(cache: kvc.KVCache, q, k, v, cur_pos, *, window,
                  ring: bool = False, cap: Optional[float] = None,
                  scale: float = 1.0):
    """Post-projection decode attention against a dense cache: cache
    update + masked softmax over the slots.  Split out from attn_decode
    so benchmarks can time the attention/KV term separately from the
    (compressible) FC projections."""
    with jax.named_scope("kv.write"):
        cache = kvc.update(cache, k, v, cur_pos, ring=ring)
    with jax.named_scope("attention"):
        mask = kvc.attention_mask(cache, cur_pos,
                                  jnp.asarray(window, jnp.int32))  # [B, S]
        o = _core(q, cache.k, cache.v, mask[:, None, None, :], cap, scale)
    return cache, o


def decode_attend_paged(pool: kvs.PagedKV, table, q, k, v, cur_pos, *,
                        window, cap: Optional[float] = None,
                        scale: float = 1.0, impl: Optional[str] = None,
                        plan=None):
    """Paged counterpart of decode_attend: quantize-into-page update +
    page-gather attention (q/k/v are [B, H(kv), 1, Dh] as from _qkv).
    ``impl`` overrides the tuner's kernel choice; with a ``plan`` the
    tuned kernel runs shard-local over the head axis via shard_map."""
    pool = kvs.update(pool, table, k[:, :, 0].astype(jnp.float32),
                      v[:, :, 0].astype(jnp.float32), cur_pos)
    if impl is None and plan is not None and plan.tp > 1:
        from repro.shard import paged_attention_sharded
        o = paged_attention_sharded(plan, q[:, :, 0], pool, table, cur_pos,
                                    jnp.asarray(window, jnp.int32),
                                    scale=scale, cap=cap)
    else:
        o = kvs.paged_attention(q[:, :, 0], pool, table, cur_pos,
                                jnp.asarray(window, jnp.int32),
                                scale=scale, cap=cap, impl=impl)
    return pool, o[:, :, None, :]


def attn_decode(p, cache: kvc.KVCache, x, cur_pos, *, n_heads: int,
                n_kv: int, d_head: int, window, ring: bool = False,
                cap: Optional[float] = None,
                theta: Optional[float] = 10000.0,
                scale: Optional[float] = None, plan=None):
    """One-token decode. x [B,1,D], cur_pos [B] absolute position."""
    scale = (d_head ** -0.5) if scale is None else scale
    q, k, v = _qkv(p, x, n_heads, n_kv, d_head, cur_pos[:, None], theta,
                   plan=plan)
    cache, o = decode_attend(cache, q, k, v, cur_pos, window=window,
                             ring=ring, cap=cap, scale=scale)
    return cache, dense(_merge_heads(o.astype(COMPUTE_DTYPE)), p["wo"],
                        plan=plan)


def attn_decode_paged(p, pool: kvs.PagedKV, table, x, cur_pos, *,
                      n_heads: int, n_kv: int, d_head: int, window,
                      cap: Optional[float] = None,
                      theta: Optional[float] = 10000.0,
                      scale: Optional[float] = None, plan=None):
    """One-token decode against the paged KV pool (cache="paged" route).

    The current token's k/v are quantized into their page first, then the
    paged-attention kernel attends over the sequence's page table — same
    write-then-attend semantics as attn_decode, O(used pages) memory.
    Windowing is mask-only here; page reclamation behind an SWA window is
    the Session's host-side job (kvstore.reclaimable_prefix).  Under a
    sharding plan the tuned kernel — Pallas included — runs shard-local
    over the head axis via `shard.paged_attention_sharded` (heads are
    independent, so mesh output is bit-identical to single-device)."""
    scale = (d_head ** -0.5) if scale is None else scale
    q, k, v = _qkv(p, x, n_heads, n_kv, d_head, cur_pos[:, None], theta,
                   plan=plan)
    pool, o = decode_attend_paged(pool, table, q, k, v, cur_pos,
                                  window=window, cap=cap, scale=scale,
                                  plan=plan)
    return pool, dense(_merge_heads(o.astype(COMPUTE_DTYPE)), p["wo"],
                       plan=plan)
