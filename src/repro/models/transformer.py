"""Block definitions + scan-over-layers stacks for every arch family.

One homogeneous block per family so the layer stack is a single
`jax.lax.scan` over stacked parameters — compact HLO (fast AOT compiles for
the 512-device dry-run), natural remat boundaries, and per-layer variation
(attention windows) threaded as scanned data, not structure.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import kvstore as kvs
from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import kvcache as kvc
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.layers import (COMPUTE_DTYPE, layer_norm, layer_norm_init,
                                 mlp, mlp_init, rms_norm, rms_norm_init,
                                 rope_tables)


def _norm(cfg: ArchConfig):
    return rms_norm if cfg.norm == "rms" else layer_norm


def _norm_init(cfg: ArchConfig, d: int):
    return rms_norm_init(d) if cfg.norm == "rms" else layer_norm_init(d)


def layer_xs(cfg: ArchConfig) -> Dict:
    """What varies from layer to layer, scanned beside the stacked
    params: the attention window (-1 = full) and, where layer kinds
    rotate differently, the RoPE table (None otherwise)."""
    return {"window": jnp.asarray(cfg.layer_windows(), jnp.int32),
            "rope": rope_tables(cfg)}


def ffn(cfg: ArchConfig, p: Dict, y, plan=None, valid=None):
    """The block's feed-forward: dense MLP or the routed expert layer.
    Returns (out, aux loss, pairs computed here or None for a dense
    MLP); ``valid`` [B, T] marks real tokens (MoE routes only those)."""
    if cfg.moe:
        return moe_mod.moe_apply(p["moe"], y, cfg.moe, valid)
    return (mlp(y, p["mlp"], cfg.act, plan=plan),
            jnp.zeros((), jnp.float32), None)


# -------------------------------------------------------------- layer init
def layer_init(cfg: ArchConfig, key) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 8)
    if cfg.family == "rwkv6":
        return {"ln1": _norm_init(cfg, d), "ln2": _norm_init(cfg, d),
                "tm": ssm.rwkv6_time_mix_init(ks[0], d, cfg.rwkv_head_dim),
                "cm": ssm.rwkv6_channel_mix_init(ks[1], d, f)}
    p = {"ln1": _norm_init(cfg, d), "ln2": _norm_init(cfg, d),
         "attn": attn.attn_init(ks[0], d, cfg.n_heads, cfg.n_kv,
                                cfg.head_dim, cfg.qkv_bias)}
    if cfg.post_norms:
        p["ln1p"] = _norm_init(cfg, d)
        p["ln2p"] = _norm_init(cfg, d)
    if cfg.moe:
        p["moe"] = moe_mod.moe_init(ks[1], d, cfg.moe)
    else:
        p["mlp"] = mlp_init(ks[1], d, f, cfg.gated_mlp, cfg.act)
    if cfg.family == "hymba":
        p["mamba"] = ssm.mamba_init(ks[2], d, cfg.ssm_state)
        p["ln_ssm"] = _norm_init(cfg, d)
    return p


def stack_init(cfg: ArchConfig, key):
    layers = [layer_init(cfg, jax.random.fold_in(key, i))
              for i in range(cfg.n_layers)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


# ------------------------------------------------------------ train blocks
def _attn_kwargs(cfg: ArchConfig):
    return dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv, d_head=cfg.head_dim,
                cap=cfg.attn_softcap, theta=cfg.rope_theta,
                scale=cfg.attn_scale)


def block_forward(cfg: ArchConfig, p: Dict, x, positions, window,
                  attn_impl: str = "einsum", unroll: bool = False,
                  rope=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One layer, training/prefill. Returns (x, aux_loss)."""
    nrm = _norm(cfg)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "rwkv6":
        b, _, d = x.shape
        zeros = jnp.zeros((b, d), x.dtype)
        h, _ = ssm.rwkv6_time_mix(p["tm"], nrm(x, p["ln1"]), zeros,
                                  d_head=cfg.rwkv_head_dim)
        x = x + h
        h, _ = ssm.rwkv6_channel_mix(p["cm"], nrm(x, p["ln2"]), zeros)
        return x + h, aux

    h = attn.attn_apply(p["attn"], nrm(x, p["ln1"]), positions,
                        window=window, causal=cfg.causal,
                        impl=attn_impl, unroll=unroll, rope_table=rope,
                        **_attn_kwargs(cfg))
    if cfg.family == "hymba":
        hs = ssm.mamba_apply(p["mamba"], nrm(x, p["ln1"]),
                             state=cfg.ssm_state)
        h = 0.5 * (nrm(h, p["ln_ssm"]) + hs.astype(COMPUTE_DTYPE))
    if cfg.post_norms:
        h = nrm(h, p["ln1p"])
    x = x + h
    h, aux, _ = ffn(cfg, p, nrm(x, p["ln2"]))
    if cfg.post_norms:
        h = nrm(h, p["ln2p"])
    return x + h, aux


def stack_forward(cfg: ArchConfig, stacked: Dict, x, positions,
                  remat: str = "dots", attn_impl: str = "einsum",
                  unroll: bool = False):
    """Scan the layer stack. Returns (x, total_aux).

    unroll=True inlines every layer (used by the roofline cost extraction:
    XLA cost_analysis counts a while-loop body ONCE, so the scanned form
    under-reports flops by ~n_layers)."""
    def body(carry, inp):
        xc, auxc = carry
        p, lx = inp
        xo, aux = block_forward(cfg, p, xc, positions, lx["window"],
                                attn_impl, unroll=unroll, rope=lx["rope"])
        return (xo, auxc + aux), None

    if remat == "full":
        body = jax.checkpoint(body, policy=None)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (stacked, layer_xs(cfg)),
                               unroll=cfg.n_layers if unroll else 1)
    return x, aux


# ----------------------------------------------------------- decode blocks
def _any_global(cfg: ArchConfig) -> bool:
    return any(w < 0 for w in cfg.layer_windows())


def init_layer_state(cfg: ArchConfig, batch: int, slots_full: int,
                     kv_cache: str = "full", page_size: int = 16,
                     kv_pool_pages: Optional[int] = None,
                     kv_dtype: str = "int8") -> Dict:
    """Per-layer decode state template (one layer; caller stacks L)."""
    if cfg.family == "rwkv6":
        h = cfg.d_model // cfg.rwkv_head_dim
        return {"tm_prev": jnp.zeros((batch, cfg.d_model), COMPUTE_DTYPE),
                "cm_prev": jnp.zeros((batch, cfg.d_model), COMPUTE_DTYPE),
                "S": jnp.zeros((batch, h, cfg.rwkv_head_dim,
                                cfg.rwkv_head_dim), jnp.float32)}
    st = {}
    if kv_cache == "paged":
        # O(used pages): every layer owns pool arrays of the same shape,
        # all indexed through the one shared per-sequence page table
        npp = -(-slots_full // page_size)
        n_pages = (1 + batch * npp if kv_pool_pages is None
                   else kv_pool_pages)
        st["kv"] = kvs.init_pool(n_pages, cfg.n_kv, page_size,
                                 cfg.head_dim, kv_dtype=kv_dtype)
    else:
        # local layers ring-cache `window` slots; global layers need
        # slots_full.  scan homogeneity: all layers share the max slot
        # count, rings mask.
        slots = slots_full if _any_global(cfg) \
            else min(cfg.window, slots_full)
        st["kv"] = kvc.init_cache(batch, cfg.n_kv, slots, cfg.head_dim)
    if cfg.family == "hymba":
        st["mamba"] = {"conv": jnp.zeros((batch, 3, cfg.d_model),
                                         jnp.float32),
                       "h": jnp.zeros((batch, cfg.d_model, cfg.ssm_state),
                                      jnp.float32)}
    return st


def init_stack_state(cfg: ArchConfig, batch: int, slots_full: int,
                     **kv_kw):
    one = init_layer_state(cfg, batch, slots_full, **kv_kw)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape),
        one)


def block_decode(cfg: ArchConfig, p: Dict, st: Dict, x, cur_pos, window,
                 page_table=None, plan=None, rope=None, layer=None):
    """One layer, one token. x [B,1,D] -> (state', x', pairs computed
    here or None).  Paged (``page_table`` given): ``st["kv"]`` is the
    whole stacked pool, written at ``layer`` (see :func:`scan_layers`).
    ``plan`` (shard.ShardingPlan) threads through to every projection so
    compressed FC runs shard-local tensor-parallel; None = the
    replicated single-device path, byte-identical to before."""
    nrm = _norm(cfg)
    if cfg.family == "rwkv6":
        tm_st = {"prev": st["tm_prev"], "S": st["S"]}
        tm_st, h = ssm.rwkv6_time_mix_decode(p["tm"], tm_st,
                                             nrm(x, p["ln1"]),
                                             d_head=cfg.rwkv_head_dim)
        x = x + h
        cm_prev, h = ssm.rwkv6_channel_mix_decode(p["cm"], st["cm_prev"],
                                                  nrm(x, p["ln2"]))
        return {"tm_prev": tm_st["prev"], "cm_prev": cm_prev,
                "S": tm_st["S"]}, x + h, None

    if page_table is not None:
        cache, h = attn.attn_decode_paged(p["attn"], st["kv"], layer,
                                          page_table, nrm(x, p["ln1"]),
                                          cur_pos,
                                          window=window, plan=plan,
                                          rope_table=rope,
                                          **_attn_kwargs(cfg))
    else:
        cache, h = attn.attn_decode(p["attn"], st["kv"], nrm(x, p["ln1"]),
                                    cur_pos, window=window,
                                    ring=not _any_global(cfg), plan=plan,
                                    rope_table=rope, **_attn_kwargs(cfg))
    new_st = dict(st)
    new_st["kv"] = cache
    if cfg.family == "hymba":
        mst, hs = ssm.mamba_decode(p["mamba"], st["mamba"],
                                   nrm(x, p["ln1"]), state=cfg.ssm_state)
        new_st["mamba"] = mst
        h = 0.5 * (nrm(h, p["ln_ssm"]) + hs.astype(COMPUTE_DTYPE))
    if cfg.post_norms:
        h = nrm(h, p["ln1p"])
    x = x + h
    h, _, pairs = ffn(cfg, p, nrm(x, p["ln2"]), plan=plan)
    if cfg.post_norms:
        h = nrm(h, p["ln2p"])
    return new_st, x + h, pairs


def scan_layers(cfg: ArchConfig, block, stacked: Dict, states, x,
                paged: bool, unroll: bool = False):
    """Scan ``block(p, st, x, lx, layer) -> (st', x', pairs)`` over the
    layers -> (states', x', per-layer pairs [L] or None).

    Params, ``layer_xs`` and every state leaf are scanned, except a
    paged pool: the stacked ``states["kv"]`` ([L, n_pages, ...]) rides in
    the carry beside ``x``, and each layer is handed it whole in
    ``st["kv"]`` with its index ``layer``, writes its pages
    (``kvstore.update_layer``) and hands it on.  Scanned in and out
    instead, XLA would slice each layer out and stack a second pool."""
    unroll = cfg.n_layers if unroll else 1
    if not paged:
        def body(xc, inp):
            p, st, lx = inp
            new_st, xo, pairs = block(p, st, xc, lx, None)
            return xo, (new_st, pairs)

        x, (new_states, pairs) = jax.lax.scan(
            body, x, (stacked, states, layer_xs(cfg)), unroll=unroll)
        return new_states, x, pairs

    states = dict(states)
    pool = states.pop("kv")

    def body(carry, inp):
        xc, pool = carry
        p, st, lx, layer = inp
        new_st, xo, pairs = block(p, {**st, "kv": pool}, xc, lx, layer)
        new_st = dict(new_st)
        pool = new_st.pop("kv")
        return (xo, pool), (new_st, pairs)

    (x, pool), (new_states, pairs) = jax.lax.scan(
        body, (x, pool),
        (stacked, states, layer_xs(cfg),
         jnp.arange(cfg.n_layers, dtype=jnp.int32)), unroll=unroll)
    return {**new_states, "kv": pool}, x, pairs


def stack_decode(cfg: ArchConfig, stacked: Dict, states, x, cur_pos,
                 unroll: bool = False, page_table=None, plan=None):
    """-> (states', x', per-layer pairs computed here [L] or None)."""
    def block(p, st, xc, lx, layer):
        return block_decode(cfg, p, st, xc, cur_pos, lx["window"],
                            page_table=page_table, plan=plan,
                            rope=lx["rope"], layer=layer)

    return scan_layers(cfg, block, stacked, states, x,
                       paged=page_table is not None, unroll=unroll)
